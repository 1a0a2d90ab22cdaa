"""Plain reference for GLM-5.2 (``model_type`` ``glm_moe_dsa``): the forward
pass over a whole sequence, in straightforward ``jax.numpy``. No cache, no
kernel, no batching, no absorbed products, no sorting of tokens by expert;
it imports nothing of ``determined_clone_tpu`` and receives its weights from
the benchmark's seeded generator (``benchmarks/adapters/glm_moe_dsa.py``),
as a tree with the leaves

    embed/table [V, D]; final_norm/scale [D]; lm_head/kernel [D, V];
    one stack a layer kind ``<mlp>_<indexer>`` (``dense_full``,
    ``sparse_shared``, ``sparse_full``), its layers in order, each with
      {ln1,ln2}/scale [., D]; q_a/kernel [., D, r_q]; q_norm/scale [., r_q];
      q_b_nope/kernel [., r_q, H n]; q_b_rope/kernel [., r_q, H r] (head
      i's ``W_QB`` = its columns of the two); kv_a/kernel [., D, r_kv + r];
      kv_norm/scale [., r_kv];
      uk/kernel [., H, n, r_kv]; uv/kernel [., H, r_kv, v] (``W_KVB`` of
      head i = ``[uk_i; uv_i^T]``); attn_out/kernel [., H v, D];
      ``*_full``: idx_q/kernel [., r_q, J d_I]; idx_k/kernel [., D, d_I];
      idx_k_norm/{scale,bias} [., d_I]; idx_w/kernel [., D, J];
      ``dense_*``: mlp_{gate,up}/kernel [., D, F]; mlp_down/kernel [., F, D];
      ``sparse_*``: router/kernel [., D, E]; router/bias [., E];
      shared_{gate,up}/kernel [., D, F_e]; shared_down/kernel [., F_e, D];
      experts_{gate,up}/kernel [., E_held, D, F_e]; experts_down/kernel
      [., E_held, F_e, D].

Written from the published configuration
(https://huggingface.co/zai-org/GLM-5.2/blob/main/config.json: hidden 6144,
64 heads, ``q_lora_rank`` 2048, ``kv_lora_rank`` 512, ``qk_nope_head_dim``
192, ``qk_rope_head_dim`` 64, ``v_head_dim`` 256, ``index_n_heads`` 32,
``index_head_dim`` 128, ``index_topk`` 2048, ``indexer_types``,
``mlp_layer_types``, 256 routed experts of width 2048, 8 a token, ``sigmoid``
scores, ``noaux_tc``, ``norm_topk_prob``, ``routed_scaling_factor`` 2.5, one
shared expert, ``rope_theta`` 8e6 with ``rope_interleave``, ``rms_norm_eps``
1e-5, untied head, no biases). ``x`` [T, D] is the float32 residual stream.

1. ``x = E[tokens]``; ``norm(x) = x / sqrt(mean(x^2) + eps) * w``. Every
   layer: ``x += Attn(norm(x))``, then ``x += FFN(norm(x))``. ``logits =
   W_head norm(x)``.
2. Attention (MLA, expanded): ``c_Q = norm(W_QA h)``; per head ``[q_N | q_R]
   = W_QB c_Q``; ``[c | k_R] = W_KVA h``, ``c = norm(c)``; ``q_R``, ``k_R``
   rotated (interleaved pairs ``(2i, 2i + 1)``, angle ``t theta^(-2i /
   64)``); per head ``k_N = W_UK c``, ``v = W_UV c``; ``p = softmax over the
   allowed s of ((q_N . k_N + q_R . k_R) / sqrt(256))``; ``o = sum p v``;
   ``y = W_O [o_1; ...; o_H]``.
3. The allowed set. In a ``full`` layer: ``q_I = W_IQ c_Q`` (J heads of
   d_I), ``k_I = LayerNorm(W_IK h)`` (scale and bias, eps 1e-6), both
   rotated on their first 64 dimensions, ``w = W_IW h``; ``I[t, s] = sum_j
   w[t, j] relu(q_I[t, j] . k_I[s])``; the allowed set of t is the
   ``index_topk`` positions ``s <= t`` of largest ``I`` (``lax.top_k``: ties
   to the lower position), all of them while ``t + 1 <= index_topk``. A
   ``shared`` layer attends the set of the nearest ``full`` layer before it.
4. FFN. ``dense``: ``W_down(silu(W_gate h) * W_up h)``. ``sparse``: ``s =
   sigmoid(W_r h)`` over all E experts; the 8 experts of largest ``s + b``
   are chosen; ``g_e = 2.5 s_e / sum over the chosen of s``; ``FFN(h) =
   Shared(h) + sum over the chosen experts that are held of g_e
   Expert_e(h)``, every expert a SwiGLU. An expert that is not held adds
   nothing (its score still took part in the normaliser): the benchmark's
   configuration holds experts 0..15 of 256, one member of an
   expert-parallel group of 16.

**The experts a served sequence took.** Which 8 experts a token goes to
is a discrete choice: where the 8th and 9th biased scores lie closer than
the program's rounding of the activation before them, program and
reference part, and the token's logits with them, by a hundred times what
arithmetic moves them. ``teacher_forced_logits`` therefore takes, for a
sequence the program served and reported on (``reference/served.py``,
filled by the adapter from the engine's ``RequestResult.token_records``),
the experts the program chose at each position (``forward(routing=)``; the
gates are still this reference's own scores of those experts), prints the
share of those choices that its own float32 scores make too, and returns
NaN where that share is under ``ROUTING_FLOOR``. Everything else, the
chosen positions among it, is this reference's own.

What the configuration does not state (the benchmark's configuration lists
these under ``assumed``): that an indexer head's rotary dimensions are its
first 64 (DeepSeek-V3.2-Exp's released indexer); no constant factors on
``w`` (positive constants change no top-k); no Hadamard rotation and no fp8
in the indexer (aids to quantisation that leave exact dot products alone);
the LayerNorm's eps (1e-6, that release's); the tie order; the selection
bias and all weights random from the seed. The prediction (MTP) layer is
not held.

``precision`` selects how matrix products are computed, as in
``reference/gpt2.py``: ``"f32"`` float32 at ``Precision.HIGHEST``, the
reference proper; ``"bf16"`` operands rounded to bfloat16, float32 sums;
``"fp8"`` operands rounded to float8 e4m3 under one scale per tensor, the
control. The selections (positions, experts) are made from the scores of
the same precision.

A weight is raised to float32 by the product that reads it, attention runs
a group of heads and a block of queries at a time, and the FFN and the head
a block of rows at a time, so that 32768 positions fit one chip beside 7.8
GB of bfloat16 weights.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import served
from benchmarks.reference.gpt2 import matmul

Params = Dict[str, Any]

# the layers the benchmark's configuration holds: published layers 2..6
MLP_TYPES = ("dense",) + ("sparse",) * 4
INDEXER_TYPES = ("full",) + ("shared",) * 3 + ("full",)
CONSTANTS = dict(index_topk=2048, experts_per_token=8, routed_scale=2.5,
                 first_expert=0, rope_theta=8e6, rms_eps=1e-5,
                 index_norm_eps=1e-6)
# the least share of a served program's choices of experts that this
# reference's own scores have to make too (PERF.md section 2 has the
# readings on both sides of it)
ROUTING_FLOOR = 0.95
# float32 logits of the served sequences checked so far by this module (one
# run's: the harness loads a reference a run), for a control that scores the
# same sample again (``tools/readings.py``): a minute of the chip a sequence
_CHECKED: Dict[Any, np.ndarray] = {}
ROWS = 2048      # rows of the FFN and of the head computed at a time
Q_BLOCK = 256    # queries of attention and of the indexer at a time
HEAD_GROUP = 8   # heads whose keys and values are expanded at a time


def rmsnorm(w: jax.Array, x: jax.Array, eps: float) -> jax.Array:
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * w.astype(jnp.float32)


def layernorm(p: Params, x: jax.Array, eps: float) -> jax.Array:
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"].astype(jnp.float32) \
        + p["bias"].astype(jnp.float32)


def rotary(x: jax.Array, theta: float) -> jax.Array:
    """x: [T, ..., d], position = row: every pair ``(x[2i], x[2i + 1])``
    turned by the angle ``t theta^(-2i / d)``."""
    T, d = x.shape[0], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs[None, :]
    ang = ang.reshape((T,) + (1,) * (x.ndim - 2) + (d // 2,))
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.zeros_like(x)
    out = out.at[..., 0::2].set(even * jnp.cos(ang) - odd * jnp.sin(ang))
    return out.at[..., 1::2].set(even * jnp.sin(ang) + odd * jnp.cos(ang))


def _by_rows(fn, *xs: jax.Array) -> Any:
    """``fn`` over the rows of every x [T, ...], a block of ``ROWS`` rows
    at a time."""
    T = xs[0].shape[0]
    rows = math.gcd(ROWS, T)
    out = jax.lax.map(lambda block: fn(*block), tuple(
        x.reshape(T // rows, rows, *x.shape[1:]) for x in xs))
    return jax.tree.map(lambda o: o.reshape(T, *o.shape[2:]), out)


def _blocks(T: int) -> Tuple[int, jax.Array]:
    qb = math.gcd(Q_BLOCK, T)
    return qb, jnp.arange(0, T, qb)


def choose_positions(lp: Params, h: jax.Array, c_q: jax.Array, rope: int,
                     c: Dict, precision: str) -> jax.Array:
    """Step 3 in a ``full`` layer: allowed [T, T] bool."""
    T = h.shape[0]
    J = lp["idx_w"]["kernel"].shape[-1]
    topk = min(c["index_topk"], T)
    q = matmul(c_q, lp["idx_q"]["kernel"], precision).reshape(T, J, -1)
    k = layernorm(lp["idx_k_norm"],
                  matmul(h, lp["idx_k"]["kernel"], precision),
                  c["index_norm_eps"])
    q = jnp.concatenate([rotary(q[..., :rope], c["rope_theta"]),
                         q[..., rope:]], axis=-1)
    k = jnp.concatenate([rotary(k[..., :rope], c["rope_theta"]),
                         k[..., rope:]], axis=-1)
    w = matmul(h, lp["idx_w"]["kernel"], precision)              # [T, J]
    qb, starts = _blocks(T)

    def one_block(lo):
        q_b = jax.lax.dynamic_slice_in_dim(q, lo, qb).transpose(1, 0, 2)
        w_b = jax.lax.dynamic_slice_in_dim(w, lo, qb)
        dots = matmul(q_b, k.T[None], precision)                 # [J, qb, T]
        score = jnp.sum(w_b.T[:, :, None] * jax.nn.relu(dots), axis=0)
        t = lo + jnp.arange(qb)
        score = jnp.where(jnp.arange(T)[None, :] <= t[:, None], score,
                          -jnp.inf)
        vals, idx = jax.lax.top_k(score, topk)
        return jnp.zeros((qb, T), bool).at[
            jnp.arange(qb)[:, None], idx].set(vals > -jnp.inf)

    return jax.lax.map(one_block, starts).reshape(T, T)


def attention(lp: Params, x: jax.Array, allowed: Optional[jax.Array],
              c: Dict, precision: str, has_indexer: bool,
              given: Optional[jax.Array]
              ) -> Tuple[jax.Array, jax.Array]:
    """Steps 2 and 3 of one layer: ``(x + y, allowed [T, T])``. ``given``
    replaces a ``full`` layer's own choice (the tests hand over the
    program's)."""
    T, D = x.shape
    H, nope, rank = lp["uk"]["kernel"].shape
    v_dim = lp["uv"]["kernel"].shape[-1]
    rope = lp["kv_a"]["kernel"].shape[-1] - rank
    eps = c["rms_eps"]
    h = rmsnorm(lp["ln1"]["scale"], x, eps)
    c_q = rmsnorm(lp["q_norm"]["scale"],
                  matmul(h, lp["q_a"]["kernel"], precision), eps)
    kv = matmul(h, lp["kv_a"]["kernel"], precision)
    lat = rmsnorm(lp["kv_norm"]["scale"], kv[:, :rank], eps)     # [T, rank]
    k_r = rotary(kv[:, rank:], c["rope_theta"])                  # [T, rope]
    if has_indexer:
        allowed = given if given is not None else choose_positions(
            lp, h, c_q, rope, c, precision)
    scale = (nope + rope) ** -0.5
    g = math.gcd(HEAD_GROUP, H)
    qb, starts = _blocks(T)
    r_q = lp["q_b_nope"]["kernel"].shape[0]
    q_bn = lp["q_b_nope"]["kernel"].reshape(r_q, H // g, g * nope)
    q_br = lp["q_b_rope"]["kernel"].reshape(r_q, H // g, g * rope)
    w_o = lp["attn_out"]["kernel"].reshape(H // g, g * v_dim, D)

    def head_group(y, group):
        w_qn, w_qr, w_uk, w_uv, w_out = group
        q_n = matmul(c_q, w_qn, precision).reshape(T, g, nope).transpose(
            1, 0, 2)                                             # [g, T, n]
        q_r = rotary(matmul(c_q, w_qr, precision).reshape(T, g, rope),
                     c["rope_theta"]).transpose(1, 0, 2)
        k_n = matmul(w_uk, lat.T[None], precision)               # [g, n, T]
        val = matmul(lat[None], w_uv, precision)                 # [g, T, v]

        def one_block(lo):
            def cut(a):
                return jax.lax.dynamic_slice_in_dim(a, lo, qb, axis=1)

            s = (matmul(cut(q_n), k_n, precision)
                 + matmul(cut(q_r), k_r.T[None], precision)) * scale
            seen = jax.lax.dynamic_slice_in_dim(allowed, lo, qb)
            p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
            return matmul(p, val, precision)                     # [g, qb, v]

        o = jax.lax.map(one_block, starts)                  # [nb, g, qb, v]
        o = o.transpose(0, 2, 1, 3).reshape(T, g * v_dim)
        return y + matmul(o, w_out, precision), None

    y, _ = jax.lax.scan(
        head_group, jnp.zeros((T, D), jnp.float32),
        (q_bn.transpose(1, 0, 2), q_br.transpose(1, 0, 2),
         lp["uk"]["kernel"].reshape(H // g, g, nope, rank),
         lp["uv"]["kernel"].reshape(H // g, g, rank, v_dim), w_o))
    return x + y, allowed


def swiglu(lp: Params, h: jax.Array, name: str, precision: str,
           e: Optional[int] = None) -> jax.Array:
    def w(part):
        kernel = lp[f"{name}_{part}"]["kernel"]
        return kernel if e is None else kernel[e]

    act = jax.nn.silu(matmul(h, w("gate"), precision)) \
        * matmul(h, w("up"), precision)
    return matmul(act, w("down"), precision)


def ffn(lp: Params, x: jax.Array, is_sparse: bool, c: Dict,
        precision: str, experts: Sequence[int],
        given: Optional[jax.Array] = None
        ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Step 4 of one layer: ``(x + FFN(norm(x)), chosen [T, k] or None)``.
    ``experts`` are the ids of the experts whose weights the ``experts_*``
    stacks hold, in order; ``chosen`` the experts this layer's own scores
    choose, a ``sparse`` layer's. ``given`` [T, k] replaces that choice
    (the served program's, ``served_routing``): the gates are still this
    layer's own scores of the experts given."""
    def rows(x, given=None):
        h = rmsnorm(lp["ln2"]["scale"], x, c["rms_eps"])
        if not is_sparse:
            return x + swiglu(lp, h, "mlp", precision), None
        s = jax.nn.sigmoid(matmul(h, lp["router"]["kernel"], precision))
        _, top = jax.lax.top_k(
            s + lp["router"]["bias"].astype(jnp.float32),
            c["experts_per_token"])
        took = jnp.zeros(s.shape, bool).at[
            jnp.arange(s.shape[0])[:, None],
            top if given is None else jnp.where(given >= 0, given, top)
        ].set(True)
        gates = c["routed_scale"] * jnp.where(took, s, 0.0) / jnp.sum(
            jnp.where(took, s, 0.0), axis=-1, keepdims=True)
        y = swiglu(lp, h, "shared", precision)
        for at, e in enumerate(experts):   # every token through every held
            y = y + gates[:, e:e + 1] * swiglu(lp, h, "experts", precision,
                                               at)
        return x + y, top

    return _by_rows(rows, x) if given is None else _by_rows(rows, x, given)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 9))
def _hidden(params, tokens, precision, mlp_types, indexer_types, constants,
            experts, choices, routing, keep_choices):
    """The final norm's output [T, D], and (``keep_choices``) every
    ``full`` layer's allowed set [L_full, T, T] and every ``sparse``
    layer's own choice of experts [L_sparse, T, k]."""
    c = dict(constants)
    x = params["embed"]["table"][tokens].astype(jnp.float32)
    seen: Dict[str, int] = {}
    allowed, chosen, routed, n_full = None, [], [], 0
    for mlp, indexer in zip(mlp_types, indexer_types):
        kind = f"{mlp}_{indexer}"
        lp = jax.tree.map(lambda w: w[seen.get(kind, 0)], params[kind])
        seen[kind] = seen.get(kind, 0) + 1
        full = indexer == "full"
        x, allowed = attention(
            lp, x, allowed, c, precision, full,
            choices[n_full] if full and choices is not None else None)
        if full:
            n_full += 1
            if keep_choices:
                chosen.append(allowed)
        sparse = mlp == "sparse"
        x, own = ffn(lp, x, sparse, c, precision, experts,
                     routing[len(routed)] if sparse and routing is not None
                     else None)
        if sparse:
            routed.append(own)
    return (rmsnorm(params["final_norm"]["scale"], x, c["rms_eps"]),
            jnp.stack(chosen) if keep_choices and chosen else None,
            jnp.stack(routed) if keep_choices and routed else None)


@functools.partial(jax.jit, static_argnums=(2,))
def _head(x, kernel, precision):
    return matmul(x, kernel, precision)


def forward(params: Params, tokens: Sequence[int], *,
            precision: str = "f32",
            mlp_types: Sequence[str] = MLP_TYPES,
            indexer_types: Sequence[str] = INDEXER_TYPES,
            experts: Optional[Sequence[int]] = None,
            choices: Optional[Any] = None, routing: Optional[Any] = None,
            keep_choices: bool = False,
            n_rows: Optional[int] = None, n_heads: Optional[int] = None,
            **constants: Any
            ) -> Tuple[np.ndarray, Optional[np.ndarray],
                       Optional[np.ndarray]]:
    """``(logits [n_rows, V], allowed [L_full, T, T] or None, routed
    [L_sparse, T, k] or None)`` of one sequence of T tokens; logits of its
    first ``n_rows`` positions (all by default). ``constants`` overrides
    ``CONSTANTS`` (the tests' toy sizes); ``experts`` names the experts the
    weights hold (default: ``first_expert ...`` for as many as the stacks
    hold; ``range(E)`` with whole stacks is the uncut layer); ``choices``
    (the shape of ``allowed``) replaces every ``full`` layer's own choice
    of positions and ``routing`` (the shape of ``routed``) every ``sparse``
    layer's own choice of experts; ``keep_choices`` returns the sets
    attended and the experts the layers' own scores choose. ``n_heads`` is
    read from the weights and ignored."""
    c = {**CONSTANTS, **constants}
    if experts is None:
        held = [params[k]["experts_gate"]["kernel"].shape[1]
                for k in params if k.startswith("sparse_")]
        experts = range(c["first_expert"],
                        c["first_expert"] + (held[0] if held else 0))
    x, chosen, routed = _hidden(
        params, jnp.asarray(tokens, jnp.int32), precision,
        tuple(mlp_types), tuple(indexer_types), tuple(sorted(c.items())),
        tuple(experts), None if choices is None else jnp.asarray(choices),
        None if routing is None else jnp.asarray(routing, jnp.int32),
        keep_choices)
    T = x.shape[0]
    n_rows = T if n_rows is None else n_rows
    rows = math.gcd(ROWS, T)
    head = params["lm_head"]["kernel"]
    logits = np.empty((n_rows, head.shape[1]), np.float32)
    for lo in range(0, n_rows, rows):    # a block of rows at a time, to the
        logits[lo:lo + rows] = np.asarray(  # host
            _head(x[lo:lo + rows], head, precision))[:n_rows - lo]
    return (logits, None if chosen is None else np.asarray(chosen),
            None if routed is None else np.asarray(routed))


def teacher_forced_logits(params: Params, tokens: Sequence[int], *,
                          n_heads: int, precision: str = "f32",
                          pad_to: int, **constants: Any) -> np.ndarray:
    """Logits [len(tokens), V] of one sequence at the published constants
    and the benchmark's five layers (``harness/serve.py`` gives none other;
    the tests' toy cell binds its own ``constants``, ``forward``'s
    keywords). Padding on the right reaches no position on its left:
    attention and the selection are causal, and the FFN is by row.

    Where the program that served this sequence said to which experts it
    sent each position (``reference/served.py``), the expert layers take
    those experts there (``forward(routing=)``), in every precision: a
    near-tie of two router scores falls either way under the program's
    rounding, and the other expert moves a logit by a hundred times what
    the arithmetic does. The share of the program's choices that this
    reference's own float32 scores make too is printed, and below
    ``ROUTING_FLOOR`` the logits are NaN: a program that routes by other
    rules than step 4's is not correct, whatever its logits."""
    key = (tuple(int(t) for t in tokens), pad_to,
           tuple(sorted(constants.items())))
    if precision == "f32" and key in _CHECKED:
        return _CHECKED[key]
    padded = list(tokens) + [0] * (pad_to - len(tokens))
    record = served.TOKEN_RECORDS.get(key[0])
    if record is None:
        return forward(params, padded, precision=precision,
                       n_rows=len(tokens), **constants)[0]
    k = {**CONSTANTS, **constants}["experts_per_token"]
    given = np.full((record.shape[1] // k, len(padded), k), -1, np.int32)
    given[:, :len(record)] = np.asarray(record).reshape(
        len(record), -1, k).transpose(1, 0, 2)
    logits, _, own = forward(params, padded, precision=precision,
                             n_rows=len(tokens), routing=given,
                             keep_choices=precision == "f32", **constants)
    if own is not None:
        shared = (given[:, :len(record), :, None]
                  == own[:, :len(record), None, :]).any(-1)
        print(f"# reference: {len(record)} positions take the program's "
              f"experts; {shared.mean():.4f} of its choices are the "
              f"reference's own (least in a layer "
              f"{shared.mean(axis=(1, 2)).min():.4f}; "
              f"{shared.all(-1).all(0).mean():.4f} of positions agree in "
              f"every layer; floor {ROUTING_FLOOR})", flush=True)
        if shared.mean() < ROUTING_FLOOR:
            logits[:] = np.nan
        _CHECKED[key] = logits
    return logits


def layer_ffn(lp: Params, x: Any, *, experts: Sequence[int],
              precision: str = "f32", **constants: Any) -> np.ndarray:
    """Step 4 alone, of one ``sparse`` layer (``lp``: its leaves, no stack
    dimension) over x [T, D], residual included: for the test that ties an
    expert-parallel member's share to the whole layer."""
    c = {**CONSTANTS, **constants}
    return np.asarray(ffn(lp, jnp.asarray(x, jnp.float32), True, c,
                          precision, tuple(experts))[0])


__all__: List[str] = ["forward", "teacher_forced_logits", "layer_ffn"]
