"""Plain reference for Kimi-Linear (``model_type`` ``kimi_linear``): the
forward pass over a whole sequence, in straightforward ``jax.numpy``. No
cache, no kernel, no batching, no chunked delta rule, no absorbed products,
no sorting of tokens by expert; it imports nothing of
``determined_clone_tpu`` and receives its weights from the benchmark's
seeded generator (``benchmarks/adapters/kimi_linear.py``), as a tree with
the leaves

    embed/table [V, D]; final_norm/scale [D]; lm_head/kernel [D, V];
    one stack a layer kind ``<attention>_<ffn>`` (``kda_dense``,
    ``kda_sparse``, ``mla_sparse``), its layers in order, each with
      {ln1,ln2}/scale [., D];
      ``kda_*``: kda_qkv/kernel [., D, 3 H d] (the heads' q, then k, then
      v); kda_conv/taps [., K, 3 H d]; kda_fa/kernel [., D, d];
      kda_fb/kernel [., d, H d]; kda_decay/{log_a [., H], dt_bias [., H
      d]}; kda_b/kernel [., D, H]; kda_ga/kernel [., D, d]; kda_gb/kernel
      [., d, H d]; kda_norm/scale [., d]; attn_out/kernel [., H d, D];
      ``mla_*``: q_nope/kernel [., D, H n]; q_rope/kernel [., D, H r];
      kv_a/kernel [., D, r_kv + r]; kv_norm/scale [., r_kv]; uk/kernel
      [., H, n, r_kv]; uv/kernel [., H, r_kv, v] (``W_KVB`` of head i =
      ``[uk_i; uv_i^T]``); attn_out/kernel [., H v, D];
      ``*_dense``: mlp_{gate,up}/kernel [., D, F]; mlp_down/kernel [., F,
      D]; ``*_sparse``: router/kernel [., D, E]; router/bias [., E];
      shared_{gate,up}/kernel [., D, F_e]; shared_down/kernel [., F_e, D];
      experts_{gate,up}/kernel [., E_held, D, F_e]; experts_down/kernel
      [., E_held, F_e, D].

Written from the published configuration
(https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json:
hidden 2304; ``linear_attn_config``: 32 heads of 128, ``short_conv_kernel_size``
4, ``kda_layers`` / ``full_attn_layers`` three to one; MLA of 32 heads,
``kv_lora_rank`` 512, ``qk_nope_head_dim`` 128, ``qk_rope_head_dim`` 64,
``v_head_dim`` 128, ``q_lora_rank`` null, ``mla_use_nope``; 256 experts of
1024, 8 a token, ``sigmoid``, ``moe_renormalize``, ``routed_scaling_factor``
2.446, one shared expert, ``first_k_dense_replace`` 1, dense FFN 9216;
``rms_norm_eps`` 1e-5; untied head) and the Kimi Linear report
(arXiv:2510.26692, "Kimi Delta Attention"; the released
``KimiDeltaAttention`` module of flash-linear-attention). ``x`` [T, D] is
the float32 residual stream.

1. ``x = E[tokens]``; ``norm(x) = x / sqrt(mean(x^2) + eps) * w``. Every
   layer: ``x += Attn(norm(x))``, then ``x += FFN(norm(x))``. ``logits =
   W_head norm(x)``.
2. KDA attention, H heads of d: ``[q | k | v] = SiLU(conv(W_qkv h))``,
   ``conv(x)_t = sum_j taps[j] x_{t - 3 + j}`` (a sum of four shifted rows,
   rows before the sequence zero, no bias); a head's ``q`` and ``k``
   divided by ``sqrt(sum of squares + 1e-6)``, ``q`` times ``d ** -0.5``;
   ``g = -exp(A_log_h) softplus(W_fb W_fa h + dt_bias)`` a channel, ``a =
   exp(g)``; ``b = sigmoid(W_b h)`` a head. Per head, position by position
   (``S_0 = 0``, ``[d, d]``):

       S' = Diag(a_t) S_{t-1};  S_t = S' + b_t k_t (v_t - S'^T k_t)^T;
       o_t = S_t^T q_t

   ``y = W_o [norm_head(o_i) * sigmoid(W_gb W_ga h)_i]_i``, the norm over
   one head's d outputs with a scale the heads share.
3. MLA attention, expanded, no rotary on any part: per head ``[q_N | q_R]
   = W_q h``; ``[c | k_R] = W_kva h``, ``c = norm(c)``; ``k_N = W_UK c``,
   ``v = W_UV c``; ``p = softmax over s <= t of ((q_N . k_N + q_R . k_R) /
   sqrt(192))``; ``o = sum p v``; ``y = W_O [o_1; ...; o_H]``.
4. FFN. ``dense``: ``W_down(silu(W_gate h) * W_up h)``. ``sparse``: ``s =
   sigmoid(W_r h)`` over all E experts; the 8 experts of largest ``s + b``
   are chosen; ``g_e = 2.446 s_e / sum over the chosen of s``; ``FFN(h) =
   Shared(h) + sum over the chosen experts that are held of g_e
   Expert_e(h)``, every expert a SwiGLU, a loop over the held experts. An
   expert that is not held adds nothing (its score still took part in the
   normaliser): the benchmark's configuration holds experts 0..127 of 256,
   one member of an expert-parallel pair.

**The experts a served sequence took**: as ``reference/glm_moe_dsa.py``
does and for its reason, ``teacher_forced_logits`` takes, for a sequence
the program served and reported on (``reference/served.py``), the experts
the program chose at each position, prints the share of those choices that
its own float32 scores make too, and returns NaN where that share is under
``ROUTING_FLOOR``.

What the configuration does not state (the benchmark's configuration lists
these under ``assumed``): no bias in the convolution or in the gates'
second matrices; the L2 norm's eps; the tie order of the top-k; all
weights, ``A_log``, ``dt_bias`` and the selection bias random from the seed.

``precision`` selects how matrix products are computed, as in
``reference/gpt2.py`` (``"f32"`` the reference proper, ``"bf16"``,
``"fp8"`` the control), and three further controls that compute every
product in float32 and change the delta rule alone: ``"bf16_decay"`` holds
the decay ``a_t`` in bfloat16 (a channel that keeps 0.999 of its state a
token then keeps all of it or 0.996: bfloat16 has 8 bits below 1),
``"bf16_state"`` rounds the state to bfloat16 after every position (an
unbiased rounding that a 128-term read-out partly averages away: at the
published widths it moves a logit by half of what the decay does, PERF.md
section 2),
``"no_delta"`` leaves the correction out (``S_t = S' + b_t k_t v_t^T``).

A weight is raised to float32 by the product that reads it; the delta rule
runs a group of heads at a time and attention a group of heads and a block
of queries at a time, the FFN and the head a block of rows at a time, and
nothing is computed past the sequence's last real position, so that 51200
positions fit one chip beside 8.6 GB of bfloat16 weights.
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

# the layers the benchmark's configuration holds: published layers 1..5
KINDS = ("kda_dense", "kda_sparse", "kda_sparse", "mla_sparse", "kda_sparse")
CONSTANTS = dict(experts_per_token=8, routed_scale=2.446, first_expert=0,
                 rms_eps=1e-5, l2_eps=1e-6)
DELTA_CONTROLS = ("bf16_decay", "bf16_state", "no_delta")
# the least share of a served program's choices of experts that this
# reference's own scores have to make too (PERF.md section 2)
ROUTING_FLOOR = 0.95
_CHECKED: Dict[Any, np.ndarray] = {}
ROWS = 2048      # rows of the FFN and of the head computed at a time
Q_BLOCK = 256    # queries of attention at a time
HEAD_GROUP = 8   # heads whose keys and values are expanded at a time
KDA_HEAD_GROUP = 8   # heads of the delta rule at a time


def _as_bf16(x: jax.Array) -> jax.Array:
    """x rounded to bfloat16's 8 exponent and 7 mantissa bits, float32
    still. Not ``astype`` there and back: the TPU compiler takes that pair
    out (it may keep excess precision), and the control then computes the
    reference to the last bit (PERF.md section 6, PR 41)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _products(precision: str) -> str:
    return "f32" if precision in DELTA_CONTROLS else precision


def rmsnorm(w: jax.Array, x: jax.Array, eps: float) -> jax.Array:
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * w.astype(jnp.float32)


def _by_rows(fn, n_real: jax.Array, *xs: jax.Array) -> Any:
    """``fn`` over the rows of every x [T, ...], a block of ``ROWS`` rows
    at a time, as far as row ``n_real`` (zeros after it)."""
    T = xs[0].shape[0]
    rows = math.gcd(ROWS, T)

    def cut(lo):
        return tuple(jax.lax.dynamic_slice_in_dim(x, lo, rows) for x in xs)

    shapes = jax.eval_shape(lambda: fn(*cut(0)))
    out = jax.tree.map(lambda s: jnp.zeros((T, *s.shape[1:]), s.dtype),
                       shapes)

    def block(i, out):
        return jax.tree.map(
            lambda o, new: jax.lax.dynamic_update_slice_in_dim(
                o, new, i * rows, 0), out, fn(*cut(i * rows)))

    return jax.lax.fori_loop(0, (n_real + rows - 1) // rows, block, out)


def short_conv(x: jax.Array, taps: jax.Array) -> jax.Array:
    """``y_t = sum_j taps[j] x_{t - K + 1 + j}`` for x [T, W], taps [K, W]:
    a sum of K shifted rows, zeros before the sequence."""
    K, T = taps.shape[0], x.shape[0]
    rows = jnp.pad(x, ((K - 1, 0), (0, 0)))
    return sum(taps[j].astype(jnp.float32) * rows[j:j + T] for j in range(K))


def delta_rule(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
               b: jax.Array, n_real: Any = None, variant: str = "f32",
               state: Optional[jax.Array] = None
               ) -> Tuple[jax.Array, jax.Array]:
    """Step 2's recurrence position by position: q, k, g [T, H, d_k], v
    [T, H, d_v], b [T, H] -> ``(o [T, H, d_v], S [H, d_k, d_v])``, as far
    as position ``n_real`` (all by default); ``state`` the state before
    position 0 (zero by default)."""
    T, H, dk = q.shape
    S = jnp.zeros((H, dk, v.shape[-1]), jnp.float32) if state is None \
        else state.astype(jnp.float32)

    def step(t, carry):
        S, out = carry
        q_t, k_t, v_t, g_t = (jax.lax.dynamic_index_in_dim(
            x, t, keepdims=False) for x in (q, k, v, g))
        b_t = jax.lax.dynamic_index_in_dim(b, t, keepdims=False)
        a_t = jnp.exp(g_t)
        if variant == "bf16_decay":
            a_t = _as_bf16(a_t)
        decayed = a_t[..., None] * S
        written = v_t if variant == "no_delta" \
            else v_t - jnp.sum(k_t[..., None] * decayed, axis=-2)
        S = decayed + k_t[..., None] * (b_t[:, None] * written)[:, None, :]
        if variant == "bf16_state":
            S = _as_bf16(S)
        o_t = jnp.sum(q_t[..., None] * S, axis=-2)
        return S, jax.lax.dynamic_update_index_in_dim(out, o_t, t, axis=0)

    S, out = jax.lax.fori_loop(
        0, T if n_real is None else n_real, step,
        (S, jnp.zeros((T, H, v.shape[-1]), jnp.float32)))
    return out, S


def kda_attention(lp: Params, x: jax.Array, c: Dict, precision: str,
                  n_real: jax.Array) -> jax.Array:
    """Step 2 of one layer: ``x + y``."""
    T, D = x.shape
    H = lp["kda_b"]["kernel"].shape[-1]
    d = lp["kda_norm"]["scale"].shape[-1]
    p = _products(precision)
    h = rmsnorm(lp["ln1"]["scale"], x, c["rms_eps"])
    grp = math.gcd(KDA_HEAD_GROUP, H)
    cols = grp * d
    low_f = matmul(h, lp["kda_fa"]["kernel"], p)                 # [T, d]
    low_g = matmul(h, lp["kda_ga"]["kernel"], p)
    b_all = jax.nn.sigmoid(matmul(h, lp["kda_b"]["kernel"], p))  # [T, H]

    def unit(a):
        return a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True)
                            + c["l2_eps"])

    y = jnp.zeros((T, D), jnp.float32)
    for lo in range(0, H, grp):           # a group of heads at a time
        def part(which):                  # this group's q, k or v
            at = which * H * d + lo * d
            rows = matmul(h, lp["kda_qkv"]["kernel"][:, at:at + cols], p)
            taps = lp["kda_conv"]["taps"][:, at:at + cols]
            return jax.nn.silu(short_conv(rows, taps)).reshape(T, grp, d)

        at = lo * d
        g = -jnp.exp(lp["kda_decay"]["log_a"][lo:lo + grp]
                     .astype(jnp.float32))[None, :, None] * jax.nn.softplus(
            (matmul(low_f, lp["kda_fb"]["kernel"][:, at:at + cols], p)
             + lp["kda_decay"]["dt_bias"][at:at + cols]
             ).reshape(T, grp, d))
        o, _ = delta_rule(unit(part(0)) * d ** -0.5, unit(part(1)), part(2),
                          g, b_all[:, lo:lo + grp], n_real, precision)
        gate = jax.nn.sigmoid(matmul(
            low_g, lp["kda_gb"]["kernel"][:, at:at + cols], p))
        o = rmsnorm(lp["kda_norm"]["scale"], o, c["rms_eps"])
        y = y + matmul(o.reshape(T, cols) * gate,
                       lp["attn_out"]["kernel"][at:at + cols], p)
    return x + y


def mla_attention(lp: Params, x: jax.Array, c: Dict, precision: str,
                  n_real: jax.Array) -> jax.Array:
    """Step 3 of one layer: ``x + y``."""
    T, D = x.shape
    H, nope, rank = lp["uk"]["kernel"].shape
    v_dim = lp["uv"]["kernel"].shape[-1]
    rope = lp["kv_a"]["kernel"].shape[-1] - rank
    p = _products(precision)
    h = rmsnorm(lp["ln1"]["scale"], x, c["rms_eps"])
    kv = matmul(h, lp["kv_a"]["kernel"], p)
    lat = rmsnorm(lp["kv_norm"]["scale"], kv[:, :rank], c["rms_eps"])
    k_r = kv[:, rank:]                                           # [T, rope]
    scale = (nope + rope) ** -0.5
    g = math.gcd(HEAD_GROUP, H)
    qb = math.gcd(Q_BLOCK, T)
    q_n = lp["q_nope"]["kernel"].reshape(D, H // g, g * nope)
    q_r = lp["q_rope"]["kernel"].reshape(D, H // g, g * rope)
    w_o = lp["attn_out"]["kernel"].reshape(H // g, g * v_dim, D)
    causal = jnp.arange(T)[None, :]

    def head_group(y, group):
        w_qn, w_qr, w_uk, w_uv, w_out = group
        qn = matmul(h, w_qn, p).reshape(T, g, nope).transpose(1, 0, 2)
        qr = matmul(h, w_qr, p).reshape(T, g, rope).transpose(1, 0, 2)
        k_n = matmul(w_uk, lat.T[None], p)                       # [g, n, T]
        val = matmul(lat[None], w_uv, p)                         # [g, T, v]

        def one_block(i, o):
            lo = i * qb

            def cut(a):
                return jax.lax.dynamic_slice_in_dim(a, lo, qb, axis=1)

            s = (matmul(cut(qn), k_n, p)
                 + matmul(cut(qr), k_r.T[None], p)) * scale
            seen = causal <= (lo + jnp.arange(qb))[:, None]
            w = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
            return jax.lax.dynamic_update_slice_in_dim(
                o, matmul(w, val, p), lo, 1)                     # [g, qb, v]

        o = jax.lax.fori_loop(0, (n_real + qb - 1) // qb, one_block,
                              jnp.zeros((g, T, v_dim), jnp.float32))
        o = o.transpose(1, 0, 2).reshape(T, g * v_dim)
        return y + matmul(o, w_out, p), None

    y, _ = jax.lax.scan(
        head_group, jnp.zeros((T, D), jnp.float32),
        (q_n.transpose(1, 0, 2), q_r.transpose(1, 0, 2),
         lp["uk"]["kernel"].reshape(H // g, g, nope, rank),
         lp["uv"]["kernel"].reshape(H // g, g, rank, v_dim), w_o))
    return x + y


def swiglu(gate: jax.Array, up: jax.Array, down: jax.Array, h: jax.Array,
           precision: str) -> jax.Array:
    act = jax.nn.silu(matmul(h, gate, precision)) * matmul(h, up, precision)
    return matmul(act, down, precision)


def ffn(lp: Params, x: jax.Array, is_sparse: bool, c: Dict,
        precision: str, experts: Sequence[int], n_real: jax.Array,
        given: Optional[jax.Array] = None
        ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Step 4 of one layer: ``(x + FFN(norm(x)), chosen [T, k] or None)``.
    ``experts`` are the ids of the experts whose weights the ``experts_*``
    stacks hold, in order; ``chosen`` the experts this layer's own scores
    choose. ``given`` [T, k] replaces that choice (the served program's):
    the gates are still this layer's own scores of the experts given."""
    p = _products(precision)
    ids = jnp.asarray(tuple(experts), jnp.int32)

    def kernels(name):
        return tuple(lp[f"{name}_{part}"]["kernel"]
                     for part in ("gate", "up", "down"))

    def rows(x, given=None):
        h = rmsnorm(lp["ln2"]["scale"], x, c["rms_eps"])
        if not is_sparse:
            return x + swiglu(*kernels("mlp"), h, p), None
        s = jax.nn.sigmoid(matmul(h, lp["router"]["kernel"], p))
        _, top = jax.lax.top_k(
            s + lp["router"]["bias"].astype(jnp.float32),
            c["experts_per_token"])
        took = jnp.zeros(s.shape, bool).at[
            jnp.arange(s.shape[0])[:, None],
            top if given is None else jnp.where(given >= 0, given, top)
        ].set(True)
        gates = c["routed_scale"] * jnp.where(took, s, 0.0) / jnp.sum(
            jnp.where(took, s, 0.0), axis=-1, keepdims=True)

        def one_expert(y, held):          # every token through every held
            e, gate, up, down = held
            weight = jax.lax.dynamic_index_in_dim(gates, e, axis=1)
            return y + weight * swiglu(gate, up, down, h, p), None

        y, _ = jax.lax.scan(one_expert, swiglu(*kernels("shared"), h, p),
                            (ids, *kernels("experts")))
        return x + y, top

    if not is_sparse:
        return _by_rows(lambda x: rows(x)[0], n_real, x), None
    return _by_rows(rows, n_real, x) if given is None \
        else _by_rows(rows, n_real, x, given)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 8))
def _hidden(params, tokens, precision, kinds, constants, experts, routing,
            n_real, keep_choices):
    """The final norm's output [T, D] and (``keep_choices``) every
    ``sparse`` layer's own choice of experts [L_sparse, T, k]."""
    c = dict(constants)
    x = params["embed"]["table"][tokens].astype(jnp.float32)
    seen: Dict[str, int] = {}
    routed = []
    for kind in kinds:
        lp = jax.tree.map(lambda w: w[seen.get(kind, 0)], params[kind])
        seen[kind] = seen.get(kind, 0) + 1
        attention = kda_attention if kind.startswith("kda") \
            else mla_attention
        x = attention(lp, x, c, precision, n_real)
        sparse = kind.endswith("sparse")
        x, own = ffn(lp, x, sparse, c, precision, experts, n_real,
                     routing[len(routed)] if sparse and routing is not None
                     else None)
        if sparse:
            routed.append(own)
    return (rmsnorm(params["final_norm"]["scale"], x, c["rms_eps"]),
            jnp.stack(routed) if keep_choices and routed else None)


@functools.partial(jax.jit, static_argnums=(2,))
def _head(x, kernel, precision):
    return matmul(x, kernel, precision)


def forward(params: Params, tokens: Sequence[int], *,
            precision: str = "f32", kinds: Sequence[str] = KINDS,
            experts: Optional[Sequence[int]] = None,
            routing: Optional[Any] = None, keep_choices: bool = False,
            n_rows: Optional[int] = None, first_row: int = 0,
            n_heads: Optional[int] = None,
            **constants: Any) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """``(logits [n_rows, V], routed [L_sparse, T, k] or None)`` of one
    sequence of T tokens; logits of its first ``n_rows`` positions (all by
    default), and nothing is computed past them; the head is applied from
    position ``first_row`` on, and the rows before it are zeros that were
    never written (at 81920 columns a sequence's 50 k rows would be 16 GB
    on the host). ``constants`` overrides
    ``CONSTANTS`` (the tests' toy sizes); ``experts`` names the experts the
    weights hold (default: ``first_expert ...`` for as many as the stacks
    hold; ``range(E)`` with whole stacks is the uncut layer); ``routing``
    (the shape of ``routed``) replaces every ``sparse`` layer's own choice
    of experts; ``keep_choices`` returns the experts the layers' own scores
    choose. ``n_heads`` is read from the weights and ignored."""
    c = {**CONSTANTS, **constants}
    if experts is None:
        held = [params[k]["experts_gate"]["kernel"].shape[1]
                for k in params if k.endswith("_sparse")]
        experts = range(c["first_expert"],
                        c["first_expert"] + (held[0] if held else 0))
    T = len(tokens)
    n_rows = T if n_rows is None else n_rows
    x, routed = _hidden(
        params, jnp.asarray(tokens, jnp.int32), precision, tuple(kinds),
        tuple(sorted(c.items())), tuple(experts),
        None if routing is None else jnp.asarray(routing, jnp.int32),
        jnp.asarray(n_rows, jnp.int32), keep_choices)
    rows = math.gcd(ROWS, T)
    head = params["lm_head"]["kernel"]
    logits = np.zeros((n_rows, head.shape[1]), np.float32)
    for lo in range(first_row // rows * rows, n_rows, rows):
        block = np.asarray(_head(x[lo:lo + rows], head,  # a block of rows
                                 _products(precision)))  # at a time
        at = max(first_row, lo)
        logits[at:lo + rows] = block[at - lo:n_rows - lo]
    return logits, None if routed is None else np.asarray(routed)


def teacher_forced_logits(params: Params, tokens: Sequence[int], *,
                          n_heads: int, precision: str = "f32",
                          pad_to: int, **constants: Any) -> np.ndarray:
    """Logits [len(tokens), V] of one sequence at the published constants
    and the benchmark's five layers (``harness/serve.py`` gives none other;
    the tests' toy cell binds its own ``constants``, ``forward``'s
    keywords). Padding on the right reaches no position on its left: the
    delta rule, the convolution and attention are causal, and the FFN is by
    row.

    Where the program that served this sequence said to which experts it
    sent each position (``reference/served.py``), the expert layers take
    those experts there, in every precision; the share of the program's
    choices that this reference's own float32 scores make too is printed,
    and below ``ROUTING_FLOOR`` the logits are NaN
    (``reference/glm_moe_dsa.py`` says why). The adapter leaves the
    prompt's length with the record: the hidden states are those of all
    the positions, the logits are computed from the prompt's last position
    on, which are the rows a served token is scored in."""
    key = (tuple(int(t) for t in tokens), pad_to,
           tuple(sorted(constants.items())))
    if precision == "f32" and key in _CHECKED:
        return _CHECKED[key]
    padded = list(tokens) + [0] * (pad_to - len(tokens))
    noted = served.TOKEN_RECORDS.get(key[0])
    if noted is None:
        return forward(params, padded, precision=precision,
                       n_rows=len(tokens), **constants)[0]
    prompt_len, record = noted
    k = {**CONSTANTS, **constants}["experts_per_token"]
    given = np.full((record.shape[1] // k, len(padded), k), -1, np.int32)
    given[:, :len(record)] = np.asarray(record).reshape(
        len(record), -1, k).transpose(1, 0, 2)
    logits, own = forward(params, padded, precision=precision,
                          n_rows=len(tokens), first_row=prompt_len - 1,
                          routing=given,
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
    x = jnp.asarray(x, jnp.float32)
    return np.asarray(ffn(lp, x, True, c, precision, tuple(experts),
                          jnp.asarray(x.shape[0], jnp.int32))[0])


__all__: List[str] = ["forward", "teacher_forced_logits", "layer_ffn",
                      "delta_rule", "short_conv"]
