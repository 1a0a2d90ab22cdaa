"""Plain reference for Arcee's Trinity (``model_type`` ``afmoe``): the
forward pass over a whole sequence, in straightforward ``jax.numpy``. No
cache, no kernel, no batching, no ring, no online softmax, no sorting of
tokens by expert; it imports nothing of ``determined_clone_tpu`` and
receives its weights from the benchmark's seeded generator
(``benchmarks/adapters/afmoe.py``), as a tree with the leaves

    embed/table [V, D]; final_norm/scale [D]; lm_head/kernel [D, V];
    one stack a layer kind ``<attention>_<ffn>`` (``sliding_dense``,
    ``sliding_sparse``, ``full_sparse``), its layers in order, each with
      {ln_in,ln_post_attn,ln_pre_mlp,ln_post_mlp}/scale [., D];
      q/kernel [., D, Hq d]; k/kernel, v/kernel [., D, Hkv d]; gate/kernel
      [., D, Hq d]; {q_norm,k_norm}/scale [., d]; attn_out/kernel [., Hq d,
      D];
      ``*_dense``: mlp_{gate,up}/kernel [., D, F]; mlp_down/kernel [., F,
      D]; ``*_sparse``: router/kernel [., D, E]; router/bias [., E];
      shared_{gate,up}/kernel [., D, F_e]; shared_down/kernel [., F_e, D];
      experts_{gate,up}/kernel [., E_held, D, F_e]; experts_down/kernel
      [., E_held, F_e, D].

Written from the published configuration
(https://huggingface.co/arcee-ai/Trinity-Large-Preview/blob/main/config.json:
hidden 3072; 48 query heads over 8 KV heads of 128; ``layer_types`` three
``sliding_attention`` (``sliding_window`` 4096) to one ``full_attention``;
``num_dense_layers`` 6 of a SwiGLU of 12288, then 256 experts of 3072, 4 a
token, ``score_func`` sigmoid, ``route_norm``, ``route_scale`` 2.448, one
shared expert; ``rope_theta`` 10000; ``rms_norm_eps`` 1e-5; ``mup_enabled``;
untied head) and the released ``afmoe`` module as ISSUE 47 wrote it down.
``x`` [T, D] is the float32 residual stream, ``N`` an RMSNorm ``x /
sqrt(mean(x^2) + eps) * w``.

1. ``x = E[tokens] * sqrt(D)`` (``mup_enabled``). Every layer: ``x +=
   N_post_attn(Attn(N_in(x)))``, then ``x += N_post_mlp(F(N_pre_mlp(x)))``.
   ``logits = W_head N_final(x)``.
2. Attention: ``q = W_q a`` as Hq heads of d, ``k = W_k a``, ``v = W_v a``
   as Hkv heads; ``g = sigmoid(W_g a)``; ``q`` and ``k`` normed a head by
   an RMSNorm over d whose one scale the heads share; in a ``sliding``
   layer rotary with theta 10000 over all d dimensions, halves rotated
   (pairs ``(i, i + d / 2)``), **none in a ``full`` layer**; scores ``q . k
   / sqrt(d)``, query head ``h`` against KV head ``h // (Hq / Hkv)``; the
   query at ``i`` attends ``j <= i``, in a ``sliding`` layer only ``i - j <
   4096``; softmax; ``Attn = W_o (g * [heads' sum of p v])``.
3. ``F`` ``dense``: ``W_down(silu(W_gate m) * W_up m)``. ``sparse``: ``s =
   sigmoid(W_r m)`` over all E experts; the 4 experts of largest ``s + b``
   are chosen; ``w_e = 2.448 s_e / (sum over the chosen of s + 1e-20)``;
   ``F(m) = Shared(m) + sum over the chosen experts that are held of w_e
   Expert_e(m)``, every expert a SwiGLU, a loop over the held experts. An
   expert that is not held adds nothing (its score still took part in the
   normaliser): the benchmark's configuration holds experts 0..31 of 256,
   one member of an expert-parallel group of eight.

**Departures from the released module**, each an ``assumed`` entry of the
benchmark's configuration: the rotary pairing and the window's edge as
above; the gate taken from the attention's normed input and applied before
``W_o``; the router's scores in float32; no grouping (``n_group =
topk_group = 1``); ties in the top-4 to the lower id; the selection bias
and all weights random from the seed; ``load_balance_coeff`` unused (no
loss); the "depth-scaled sandwich norm" read as an initialisation of the
post-norms' gains, which seeded gains do not model.

**The experts a served sequence took**: as ``reference/glm_moe_dsa.py``
does and for its reason, ``teacher_forced_logits`` takes, for a sequence
the program served and reported on (``reference/served.py``), the experts
the program chose at each position, prints the share of those choices that
its own float32 scores make too, and returns NaN where that share is under
``ROUTING_FLOOR``.

``precision`` selects how matrix products are computed, as in
``reference/gpt2.py`` (``"f32"`` the reference proper, ``"bf16"``,
``"fp8"`` the control), and three further controls that compute every
product in float32 and change one piece of the mathematics:
``"no_window"`` lets the sliding layers attend every earlier position,
``"rope_on_full"`` rotates q and k in the full layers too, ``"no_gate"``
leaves the output gate out.

A weight is raised to float32 by the product that reads it; attention runs
a KV head's group of query heads and a block of queries at a time over all
keys under the mask, the FFN and the head a block of rows at a time, and
nothing is computed past the sequence's last real position, so that 34816
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

# the layers the benchmark's configuration holds: published layers 6..10
KINDS = ("sliding_dense", "sliding_sparse", "full_sparse", "sliding_sparse",
         "sliding_sparse")
CONSTANTS = dict(experts_per_token=4, routed_scale=2.448, first_expert=0,
                 rms_eps=1e-5, window=4096, rope_theta=10000.0, mup=True)
STRUCTURE_CONTROLS = ("no_window", "rope_on_full", "no_gate")
# the least share of a served program's choices of experts that this
# reference's own scores have to make too (PERF.md section 2)
ROUTING_FLOOR = 0.95
_CHECKED: Dict[Any, np.ndarray] = {}
ROWS = 2048      # rows of the FFN and of the head computed at a time
Q_BLOCK = 256    # queries of attention at a time


def _products(precision: str) -> str:
    return "f32" if precision in STRUCTURE_CONTROLS else precision


def rmsnorm(w: jax.Array, x: jax.Array, eps: float) -> jax.Array:
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * w.astype(jnp.float32)


def rotary(x: jax.Array, theta: float) -> jax.Array:
    """x [T, ..., d] at positions 0..T - 1: dimension ``i < d / 2`` is
    paired with ``i + d / 2`` and the pair turned by ``t * theta ** (-2 i
    / d)``."""
    T, d = x.shape[0], x.shape[-1]
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] \
        * theta ** (-jnp.arange(d // 2, dtype=jnp.float32) * 2 / d)
    angle = angle.reshape(T, *([1] * (x.ndim - 2)), d // 2)
    lo, hi = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([lo * jnp.cos(angle) - hi * jnp.sin(angle),
                            lo * jnp.sin(angle) + hi * jnp.cos(angle)], -1)


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


def attention(lp: Params, x: jax.Array, sliding: bool, c: Dict,
              precision: str, n_real: jax.Array) -> jax.Array:
    """Step 2 of one layer: ``x + N_post_attn(Attn(N_in(x)))``."""
    T, D = x.shape
    d = lp["q_norm"]["scale"].shape[-1]
    Hq = lp["q"]["kernel"].shape[-1] // d
    Hkv = lp["k"]["kernel"].shape[-1] // d
    G = Hq // Hkv
    p = _products(precision)
    a = rmsnorm(lp["ln_in"]["scale"], x, c["rms_eps"])
    turned = sliding or precision == "rope_on_full"
    windowed = sliding and precision != "no_window"
    qb = math.gcd(Q_BLOCK, T)
    keys = jnp.arange(T)[None, :]

    def kv_head(y, group):
        w_q, w_k, w_v, w_g, w_o = group
        q = rmsnorm(lp["q_norm"]["scale"],
                    matmul(a, w_q, p).reshape(T, G, d), c["rms_eps"])
        k = rmsnorm(lp["k_norm"]["scale"], matmul(a, w_k, p), c["rms_eps"])
        v = matmul(a, w_v, p)                                     # [T, d]
        if turned:
            q, k = rotary(q, c["rope_theta"]), rotary(k, c["rope_theta"])
        q = q.transpose(1, 0, 2)                                  # [G, T, d]

        def one_block(i, o):
            lo = i * qb
            at = (lo + jnp.arange(qb))[:, None]
            s = matmul(jax.lax.dynamic_slice_in_dim(q, lo, qb, axis=1),
                       k.T[None], p) * d ** -0.5                  # [G, qb, T]
            seen = keys <= at
            if windowed:
                seen &= at - keys < c["window"]
            w = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
            return jax.lax.dynamic_update_slice_in_dim(
                o, matmul(w, v[None], p), lo, 1)

        o = jax.lax.fori_loop(0, (n_real + qb - 1) // qb, one_block,
                              jnp.zeros((G, T, d), jnp.float32))
        o = o.transpose(1, 0, 2).reshape(T, G * d)
        if precision != "no_gate":
            o = o * jax.nn.sigmoid(matmul(a, w_g, p))
        return y + matmul(o, w_o, p), None

    def groups(name):      # [D, Hq d] -> [Hkv, D, G d]: a KV head's heads
        return lp[name]["kernel"].reshape(D, Hkv, G * d).transpose(1, 0, 2)

    y, _ = jax.lax.scan(
        kv_head, jnp.zeros((T, D), jnp.float32),
        (groups("q"), lp["k"]["kernel"].reshape(D, Hkv, d).transpose(1, 0, 2),
         lp["v"]["kernel"].reshape(D, Hkv, d).transpose(1, 0, 2),
         groups("gate"), lp["attn_out"]["kernel"].reshape(Hkv, G * d, D)))
    return x + rmsnorm(lp["ln_post_attn"]["scale"], y, c["rms_eps"])


def swiglu(gate: jax.Array, up: jax.Array, down: jax.Array, h: jax.Array,
           precision: str) -> jax.Array:
    act = jax.nn.silu(matmul(h, gate, precision)) * matmul(h, up, precision)
    return matmul(act, down, precision)


def routed_sum(lp: Params, m: jax.Array, c: Dict, p: str, ids: jax.Array,
               given: Optional[jax.Array] = None
               ) -> Tuple[jax.Array, jax.Array]:
    """``(F(m), chosen [T, k])`` of one ``sparse`` layer over normed rows m
    [T, D]: the shared expert and the held experts' weighted sum."""
    def kernels(name):
        return tuple(lp[f"{name}_{part}"]["kernel"]
                     for part in ("gate", "up", "down"))

    s = jax.nn.sigmoid(matmul(m, lp["router"]["kernel"], p))
    _, top = jax.lax.top_k(s + lp["router"]["bias"].astype(jnp.float32),
                           c["experts_per_token"])
    took = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None],
        top if given is None else jnp.where(given >= 0, given, top)
    ].set(True)
    weights = c["routed_scale"] * jnp.where(took, s, 0.0) / (jnp.sum(
        jnp.where(took, s, 0.0), axis=-1, keepdims=True) + 1e-20)

    def one_expert(y, held):              # every token through every held
        e, gate, up, down = held
        weight = jax.lax.dynamic_index_in_dim(weights, e, axis=1)
        return y + weight * swiglu(gate, up, down, m, p), None

    f, _ = jax.lax.scan(one_expert, swiglu(*kernels("shared"), m, p),
                        (ids, *kernels("experts")))
    return f, top


def ffn(lp: Params, x: jax.Array, is_sparse: bool, c: Dict,
        precision: str, experts: Sequence[int], n_real: jax.Array,
        given: Optional[jax.Array] = None
        ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Step 3 of one layer: ``(x + N_post_mlp(F(N_pre_mlp(x))), chosen [T,
    k] or None)``. ``experts`` are the ids of the experts whose weights the
    ``experts_*`` stacks hold, in order; ``chosen`` the experts this
    layer's own scores choose. ``given`` [T, k] replaces that choice (the
    served program's): the weights are still this layer's own scores of
    the experts given."""
    p = _products(precision)
    ids = jnp.asarray(tuple(experts), jnp.int32)

    def rows(x, given=None):
        m = rmsnorm(lp["ln_pre_mlp"]["scale"], x, c["rms_eps"])
        if is_sparse:
            f, top = routed_sum(lp, m, c, p, ids, given)
        else:
            f, top = swiglu(*(lp[f"mlp_{part}"]["kernel"]
                              for part in ("gate", "up", "down")), m, p), None
        return x + rmsnorm(lp["ln_post_mlp"]["scale"], f, c["rms_eps"]), top

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
    if c["mup"]:
        x = x * x.shape[-1] ** 0.5
    seen: Dict[str, int] = {}
    routed = []
    for kind in kinds:
        lp = jax.tree.map(lambda w: w[seen.get(kind, 0)], params[kind])
        seen[kind] = seen.get(kind, 0) + 1
        x = attention(lp, x, kind.startswith("sliding"), c, precision,
                      n_real)
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
    never written. ``constants`` overrides ``CONSTANTS`` (the tests' toy
    sizes); ``experts`` names the experts the weights hold (default:
    ``first_expert ...`` for as many as the stacks hold; ``range(E)`` with
    whole stacks is the uncut layer); ``routing`` (the shape of ``routed``)
    replaces every ``sparse`` layer's own choice of experts;
    ``keep_choices`` returns the experts the layers' own scores choose.
    ``n_heads`` is read from the weights and ignored."""
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
    keywords). Padding on the right reaches no position on its left:
    attention is causal and everything else is by row.

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
              precision: str = "f32", whole: bool = True,
              **constants: Any) -> np.ndarray:
    """Step 3 alone, of one ``sparse`` layer (``lp``: its leaves, no stack
    dimension) over x [T, D]: ``x + N_post_mlp(F(N_pre_mlp(x)))`` or, with
    ``whole`` False, ``F(N_pre_mlp(x))`` alone, the sum that the members'
    shares add up to (the post-norm is of the sum, not a sum of parts): for
    the test that ties an expert-parallel member's share to the layer."""
    c = {**CONSTANTS, **constants}
    x = jnp.asarray(x, jnp.float32)
    if whole:
        return np.asarray(ffn(lp, x, True, c, precision, tuple(experts),
                              jnp.asarray(x.shape[0], jnp.int32))[0])
    return np.asarray(routed_sum(
        lp, rmsnorm(lp["ln_pre_mlp"]["scale"], x, c["rms_eps"]), c,
        _products(precision), jnp.asarray(tuple(experts), jnp.int32))[0])


__all__: List[str] = ["forward", "teacher_forced_logits", "layer_ffn",
                      "rotary"]
