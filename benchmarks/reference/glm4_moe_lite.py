"""Plain reference for ``glm4_moe_lite`` (GLM-4.7-Flash) on the training
path: forward, the two losses, gradients by ``jax.grad`` of that plain
forward, the clip, AdamW and the selection bias's update written out, in
straightforward ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``. No kernel, no tile loop, no
custom gradient. It imports nothing of ``determined_clone_tpu``.

Written from the published configuration
(https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json) and
the papers its layers follow (DeepSeek-V2, arXiv:2405.04434, section 2.1:
latent attention; DeepSeek-V3, arXiv:2412.19437, sections 2.1.2: sigmoid
routing with an auxiliary-loss-free selection bias, and 2.2: multi-token
prediction). ``x`` is the residual stream; a layer is ``x += Attn(norm(x))``
then ``x += FFN(norm(x))`` with RMSNorm (eps 1e-5).

- Attention, H heads: ``q = W_QB norm(W_QA h)``, a head ``[q_N | q_R]``;
  ``[c | k_R] = W_KVA h``, ``c`` normed, ``k_R`` shared by the heads;
  rotary on ``q_R`` and ``k_R``; ``[k_N | v] = W_KVB c`` a head; ``P =
  softmax(([q_N | q_R] . [k_N | k_R]) / sqrt(d_qk) + causal mask)``, ``y =
  W_O concat_h(P v)``; a block of queries at a time, recomputed in the
  backward pass, so that no ``[T, T]`` block is kept.
- FFN: a dense layer ``W_down (silu(W_gate h) * W_up h)``; an expert layer
  ``Shared(h) + sum over the token's chosen and held experts of g_e
  Expert_e(h)``: ``s = sigmoid(h W_r)``, the ``TOP_K`` experts of largest
  ``s + b`` of all the router's, ``g = ROUTED_SCALE * s / sum of the chosen
  s``; a loop over the held experts ``[first_expert, first_expert + n_held)``
  with a 0 / 1 mask of pairs. Pairs of absent experts add nothing.
- The prediction module: ``h' = W_EH [norm_e(Emb(t_{i+1})) ; norm_h(x_i)]``
  (``x_i`` the stack's output before the final norm), one expert layer, a
  final norm of its own, the model's head. ``L = mean_i CE(t_{i+1} | x_i) +
  MTP_WEIGHT * mean_{i has t_{i+2}} CE(t_{i+2} | h'_i)``.
- After AdamW (no decay on the bias, whose gradient is zero):
  ``b_e += BIAS_RATE * sign(mean load - load_e)``, ``load_e`` the tokens of
  the step that chose expert ``e`` among all the router's.

Not in the configuration, and so constants here (the configuration file's
``assumed`` says where each is from): interleaved rotary pairs,
``BIAS_RATE``, ``MTP_WEIGHT`` and the order of the concatenation, no
sequence-wise balance loss.

**Routing replay.** Which experts a token takes is a discrete choice from
scores that the program computed from bfloat16-rounded activations: two
scores within rounding fall either way, and the gradient of an expert stack
moves by far more than any rounding. Where the program handed its choices
over (``reference/replayed.py``), a token takes the program's experts **if
they are admissible**: every one of them has a biased score within
``TIE_EPS`` of this reference's own ``TOP_K``-th largest. Otherwise, and
where nothing was handed over, it takes its own. So a program that chooses
by other scores (without the bias, say) is not followed, and differs. The
gates are from the reference's own scores either way. Each step prints how
many tokens' own choice is the replayed one, beside ``AGREE_FLOOR``.

``precision``: ``"f32"`` the reference proper; ``"bf16"`` every product's
operands rounded to bfloat16 (what the configuration states for the
program); ``"fp8"`` operands in float8 e4m3 and products' incoming
gradients in e5m2 (``reference/gpt2.py:matmul``), the nearest precision
below, for the control; ``"nobias"`` float32, but the selection leaves the
bias out and nothing is replayed: the control for a program that forgot it.
The router's product is float32 under every precision.

Tree of weights (``adapters/glm4_moe_lite.py``): ``embed/table [V, D]``;
``dense/...`` and ``sparse/...`` stacks with a leading layer dimension
(``models/glm_moe_lite.py:layer_shapes`` lists the leaves); ``mtp/{enorm,
hnorm,final_norm}/scale``, ``mtp/eh_proj/kernel [2 D, D]``, ``mtp/layer/...``
(one expert layer); ``final_norm/scale``; ``lm_head/kernel [D, V]``.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference import replayed
from benchmarks.reference.gpt2 import clip_by_global_norm, leaf_norms, matmul

Params = Dict[str, Any]

PRECISIONS = ("f32", "bf16", "fp8", "nobias")
RMS_EPS = 1e-5
ROPE_BASE = 1e6
TOP_K = 4
ROUTED_SCALE = 1.8
BIAS_RATE = 1e-3
MTP_WEIGHT = 0.3
QUERY_BLOCK = 512
# a replayed choice is taken where each of its experts is within this of
# the reference's own k-th largest biased score: the scores are sigmoids of
# a logit of about unit size, which the program computes from a residual
# stream that bfloat16 products have moved by up to a percent
TIE_EPS = 5e-3
AGREE_FLOOR = 0.9


def rmsnorm(scale: jax.Array, x: jax.Array) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + RMS_EPS) * scale


def rotary(x: jax.Array) -> jax.Array:
    """x [B, T, ..., d]: pairs ``(x[2i], x[2i + 1])`` turned by ``t *
    ROPE_BASE^(-2i / d)`` at position t."""
    T, d = x.shape[1], x.shape[-1]
    freqs = ROPE_BASE ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs[None, :]
    ang = ang.reshape((1, T) + (1,) * (x.ndim - 3) + (d // 2,))
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      a * jnp.sin(ang) + b * jnp.cos(ang)],
                     axis=-1).reshape(x.shape)


def causal_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     precision: str) -> jax.Array:
    """q, k [B, H, T, d], v [B, H, T, dv] -> [B, H, T, dv]; a block of
    queries at a time."""
    B, H, T, d = q.shape
    block = min(QUERY_BLOCK, T)
    if T % block:
        raise ValueError(f"{T} positions are no multiple of {block}")

    @jax.checkpoint
    def one(start, q_block):
        scores = matmul(q_block, k.transpose(0, 1, 3, 2), precision) \
            / jnp.sqrt(jnp.float32(d))
        rows = start + jnp.arange(block)
        scores = jnp.where(rows[:, None] >= jnp.arange(T)[None, :], scores,
                           -jnp.inf)
        return matmul(jax.nn.softmax(scores, axis=-1), v, precision)

    blocks = q.reshape(B, H, T // block, block, d).transpose(2, 0, 1, 3, 4)
    out = jax.lax.map(lambda a: one(*a),
                      (jnp.arange(T // block) * block, blocks))
    return out.transpose(1, 2, 0, 3, 4).reshape(B, H, T, -1)


def attention(lp: Params, x: jax.Array, n_heads: int, precision: str
              ) -> jax.Array:
    B, T, _ = x.shape
    rank = lp["kv_norm"]["scale"].shape[-1]
    rope = lp["kv_a"]["kernel"].shape[-1] - rank
    h = rmsnorm(lp["ln1"]["scale"], x)
    c_q = rmsnorm(lp["q_norm"]["scale"],
                  matmul(h, lp["q_a"]["kernel"], precision))
    q = matmul(c_q, lp["q_b"]["kernel"], precision).reshape(B, T, n_heads, -1)
    nope = q.shape[-1] - rope
    q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:])], axis=-1)
    kv = matmul(h, lp["kv_a"]["kernel"], precision)
    c = rmsnorm(lp["kv_norm"]["scale"], kv[..., :rank])
    k_rope = rotary(kv[..., rank:])                                # [B, T, r]
    kv = matmul(c, lp["kv_b"]["kernel"], precision).reshape(
        B, T, n_heads, -1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_rope[:, :, None, :], (B, T, n_heads, rope))], axis=-1)
    o = causal_attention(*(t.transpose(0, 2, 1, 3)
                           for t in (q, k, kv[..., nope:])), precision)
    return x + matmul(o.transpose(0, 2, 1, 3).reshape(B, T, -1),
                      lp["attn_out"]["kernel"], precision)


def swiglu(gate: jax.Array, up: jax.Array, down: jax.Array, h: jax.Array,
           precision: str) -> jax.Array:
    return matmul(jax.nn.silu(matmul(h, gate, precision))
                  * matmul(h, up, precision), down, precision)


def choose(router: Params, h: jax.Array, replay: Optional[jax.Array],
           use_bias: bool = True) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """h [N, D] -> ``(experts [N, k], gates [N, k], [own choice is the
    replayed one, replayed choice admissible] as shares of the tokens)``."""
    scores = jax.nn.sigmoid(matmul(h, router["kernel"], "f32"))
    biased = scores + router["bias"] if use_bias else scores
    kth, own = jax.lax.top_k(biased, TOP_K)
    experts, shares = own, jnp.ones((2,), jnp.float32)
    if replay is not None:
        admissible = jnp.min(jnp.take_along_axis(biased, replay, axis=-1),
                             axis=-1) >= kth[:, -1] - TIE_EPS
        same = jnp.all(jnp.sort(own, axis=-1) == jnp.sort(replay, axis=-1),
                       axis=-1)
        experts = jnp.where(admissible[:, None], replay, own)
        shares = jnp.stack([jnp.mean(same.astype(jnp.float32)),
                            jnp.mean(admissible.astype(jnp.float32))])
    chosen = jnp.take_along_axis(scores, experts, axis=-1)
    return experts, ROUTED_SCALE * chosen / jnp.sum(
        chosen, axis=-1, keepdims=True), shares


def expert_layer(lp: Params, h: jax.Array, *, first_expert: int,
                 replay: Optional[jax.Array] = None, precision: str = "f32",
                 use_bias: bool = True
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """h [N, D] -> ``(Shared(h) + the held experts' part [N, D], load
    [n_experts], the two shares of :func:`choose`)``."""
    experts, gates, shares = choose(lp["router"], h, replay, use_bias)
    y = swiglu(*(lp[f"shared_{n}"]["kernel"] for n in ("gate", "up", "down")),
               h, precision)
    for e in range(lp["experts_gate"]["kernel"].shape[0]):
        weight = jnp.sum(jnp.where(experts == first_expert + e, gates, 0.0),
                         axis=-1)
        y = y + weight[:, None] * swiglu(
            *(lp[f"experts_{n}"]["kernel"][e] for n in ("gate", "up", "down")),
            h, precision)
    n_experts = lp["router"]["kernel"].shape[-1]
    load = jnp.sum(jax.nn.one_hot(experts.reshape(-1), n_experts,
                                  dtype=jnp.float32), axis=0)
    return y, load, shares


def run(stack: Params, x: jax.Array, *, n_heads: int, first_expert: int,
        replay: Optional[jax.Array], precision: str, use_bias: bool = True
        ) -> Tuple[jax.Array, Any]:
    """A stack of layers of one kind (dense where it has ``mlp_gate``), a
    layer recomputed in the backward pass; ``(x, (loads [L, E], shares [L,
    2]) of an expert stack)``."""
    B, T, D = x.shape

    @jax.checkpoint
    def layer(x, inputs):
        lp, chosen = inputs
        x = attention(lp, x, n_heads, precision)
        h = rmsnorm(lp["ln2"]["scale"], x)
        if "mlp_gate" in lp:
            return x + swiglu(*(lp[f"mlp_{n}"]["kernel"]
                                for n in ("gate", "up", "down")), h,
                              precision), None
        y, load, shares = expert_layer(
            lp, h.reshape(B * T, D), first_expert=first_expert,
            replay=chosen, precision=precision, use_bias=use_bias)
        return x + y.reshape(B, T, D), (load, shares)

    return jax.lax.scan(layer, x, (stack, replay))


def head_loss(norm: jax.Array, head: jax.Array, x: jax.Array,
              targets: jax.Array, weights: jax.Array, precision: str
              ) -> jax.Array:
    """Sum over the positions of ``weights * CE``; the logits are made
    again in the backward pass."""
    @jax.checkpoint
    def summed(norm, head, x):
        logits = matmul(rmsnorm(norm, x), head, precision)
        picked = jnp.take_along_axis(logits, targets[..., None],
                                     axis=-1)[..., 0]
        return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - picked)
                       * weights)

    return summed(norm, head, x)


def summed_losses(params: Params, batch: jax.Array, *, n_heads: int,
                  first_expert: int = 0,
                  replay: Optional[Dict[str, jax.Array]] = None,
                  precision: str = "f32", mtp_inputs_shift: int = 1
                  ) -> Tuple[jax.Array, jax.Array, Dict[str, Any]]:
    """batch int32 [B, T + 1] -> ``(sum over every position of CE(t_{i+1}),
    sum over the positions that have one of CE(t_{i+2}), {stack: (loads,
    shares)})``. (``mtp_inputs_shift`` 0 feeds the prediction module ``t_i``
    where it should get ``t_{i+1}``: the tests' wrong model.)"""
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    use_bias = precision != "nobias"
    if not use_bias:  # float32, its own choices: nothing is replayed
        precision, replay = "f32", None
    replay = replay or {}
    tokens, targets = batch[:, :-1], batch[:, 1:]
    common = dict(n_heads=n_heads, first_expert=first_expert,
                  precision=precision, use_bias=use_bias)
    table = params["embed"]["table"]
    x = table[tokens]
    stats: Dict[str, Any] = {}
    if "dense" in params:
        x, _ = run(params["dense"], x, replay=None, **common)
    if "sparse" in params:
        x, stats["sparse"] = run(params["sparse"], x,
                                 replay=replay.get("sparse"), **common)
    head = params["lm_head"]["kernel"]
    ones = jnp.ones(targets.shape, jnp.float32)
    loss_next = head_loss(params["final_norm"]["scale"], head, x, targets,
                          ones, precision)
    loss_mtp = jnp.zeros((), jnp.float32)
    if "mtp" in params:
        mp = params["mtp"]
        fed = batch[:, mtp_inputs_shift:][:, :tokens.shape[1]]
        h = matmul(jnp.concatenate(
            [rmsnorm(mp["enorm"]["scale"], table[fed]),
             rmsnorm(mp["hnorm"]["scale"], x)], axis=-1),
            mp["eh_proj"]["kernel"], precision)
        h, stats["mtp"] = run(mp["layer"], h, replay=replay.get("mtp"),
                              **common)
        loss_mtp = head_loss(
            mp["final_norm"]["scale"], head, h, jnp.roll(targets, -1, axis=1),
            ones.at[:, -1].set(0.0), precision)
    return loss_next, loss_mtp, stats


def loss_and_grads(params: Params, batch: jax.Array, *, n_heads: int,
                   first_expert: int = 0,
                   replay: Optional[Dict[str, jax.Array]] = None,
                   precision: str = "f32", rows_per_block: int = 1,
                   mtp_inputs_shift: int = 1
                   ) -> Tuple[jax.Array, Params, Dict[str, Any]]:
    """``(L, dL / dparams, {"losses": [next, mtp], stack: (loads [L, E],
    shares [L, 2])})``, ``rows_per_block`` sequences at a time."""
    n_rows, width = batch.shape
    if n_rows % rows_per_block:
        raise ValueError(f"{n_rows} rows not divisible by {rows_per_block}")
    n_blocks = n_rows // rows_per_block
    T = width - 1
    scale = jnp.asarray([1.0 / (n_rows * T),
                         1.0 / (n_rows * max(T - 1, 1))], jnp.float32)

    def total(p, rows, chosen):
        nxt, mtp, stats = summed_losses(
            p, rows, n_heads=n_heads, first_expert=first_expert,
            replay=chosen, precision=precision,
            mtp_inputs_shift=mtp_inputs_shift)
        losses = jnp.stack([nxt, mtp]) * scale
        return losses[0] + MTP_WEIGHT * losses[1], (losses, stats)

    loss = 0.0
    grads = out = None
    with jax.default_matmul_precision("highest"):
        for i in range(n_blocks):
            rows = batch[i * rows_per_block:(i + 1) * rows_per_block]
            chosen = None if replay is None else {
                name: e.reshape(e.shape[0], n_rows, T, -1)[
                    :, i * rows_per_block:(i + 1) * rows_per_block].reshape(
                    e.shape[0], rows_per_block * T, -1)
                for name, e in replay.items()}
            (value, (losses, stats)), g = jax.value_and_grad(
                total, has_aux=True)(params, rows, chosen)
            stats = {name: (load, shares / n_blocks)
                     for name, (load, shares) in stats.items()}
            block = {"losses": losses, **stats}
            loss = loss + value
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
            out = block if out is None else jax.tree.map(jnp.add, out, block)
    return loss, grads, out


def _is_bias(path: Tuple[Any, ...]) -> bool:
    return [getattr(k, "key", None) for k in path[-2:]] == ["router", "bias"]


def adamw_leaf(p, g, m, v, count, *, lr, b1, b2, eps, weight_decay):
    """AdamW (Loshchilov & Hutter 2019) on one leaf; ``count`` from 1."""
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    step = (m / (1 - b1 ** count)) / (jnp.sqrt(v / (1 - b2 ** count)) + eps)
    return p - lr * (step + weight_decay * p), m, v


def moved_bias(bias: jax.Array, load: jax.Array) -> jax.Array:
    return bias + BIAS_RATE * jnp.sign(
        jnp.mean(load, axis=-1, keepdims=True) - load)


def train_three_steps(params: Params, batches: Sequence[jax.Array], *,
                      n_heads: int, optimizer: Dict[str, float],
                      precision: str = "f32", rows_per_block: int = 1,
                      probe: Any = None, probe_arg: Any = None
                      ) -> Dict[str, Any]:
    """Follow the first steps of training on ``batches`` (one a step);
    ``reference/gpt2.py:train_three_steps`` says what is returned. The
    total ``L`` is the loss; the selection biases are leaves like any other
    (gradient 0, moved by the loads), so their change is compared too.

    At the cell's size the state does not fit the chip five times over:
    the initial weights and Adam's two moments wait on the host, and a step
    updates a leaf at a time, in the leaf's own memory: ``params`` is used
    up."""
    opt = {k: float(v) for k, v in optimizer.items()}
    clip = opt.pop("clip_global_norm")

    def gradients(p, batch, chosen, arg, first_expert, probing):
        loss, g, out = loss_and_grads(
            p, batch, n_heads=n_heads, first_expert=first_expert,
            replay=chosen, precision=precision, rows_per_block=rows_per_block)
        g = clip_by_global_norm(g, clip)
        probed = probe(g, arg) if probing else jnp.zeros(())
        return loss, g, out, leaf_norms(g), probed

    gradients = jax.jit(gradients, static_argnames=("first_expert",
                                                    "probing"))
    update = jax.jit(adamw_leaf, donate_argnums=(0, 2, 3),
                     static_argnames=tuple(opt))
    move = jax.jit(moved_bias, donate_argnums=(0,))
    paths = [path for path, _ in jax.tree_util.tree_flatten_with_path(
        params)[0]]
    treedef = jax.tree.structure(params)
    initial = [np.array(x) for x in jax.tree.leaves(params)]
    leaves = list(jax.tree.leaves(params))
    moments: List[Any] = [None] * len(leaves)
    losses: List[float] = []
    first_grad = first_probe = None
    for i, batch in enumerate(batches, start=1):
        handed = replayed.ROUTING.get(replayed.key(batch))
        chosen = None if handed is None else {
            k: jnp.asarray(v) for k, v in handed["experts"].items()}
        loss, grads, out, gnorms, probed = gradients(
            jax.tree.unflatten(treedef, leaves), jnp.asarray(batch), chosen,
            probe_arg, first_expert=0 if handed is None
            else int(handed["first_expert"]),
            probing=probe is not None and i == 1)
        losses.append(float(loss))
        agree = {k: np.asarray(v[1]).min(axis=0).round(4).tolist()
                 for k, v in out.items() if k != "losses"}
        print(f"# reference step {i} ({precision}): losses "
              f"{np.asarray(out['losses']).tolist()}; tokens whose own "
              f"choice is the replayed one, and whose replayed choice is "
              f"admissible, in the worst layer: {agree} (floor "
              f"{AGREE_FLOOR}{'' if handed else '; nothing handed over'})",
              flush=True)
        if first_grad is None:
            first_grad = jax.device_get(gnorms)
            first_probe = jax.device_get(probed)
        loads = {"sparse": out.get("sparse"), "layer": out.get("mtp")}
        grads = jax.tree.leaves(grads)
        for j, path in enumerate(paths):
            p, g = leaves[j], grads[j]
            grads[j] = None
            m, v = moments[j] if moments[j] is not None \
                else (jnp.zeros_like(p), jnp.zeros_like(p))
            decay = 0.0 if _is_bias(path) else opt["weight_decay"]
            p, m, v = update(p, g, m, v, jnp.float32(i),
                             **{**opt, "weight_decay": decay})
            if _is_bias(path):
                p = move(p, loads[path[-3].key][0])
            leaves[j] = p
            moments[j] = (np.array(m), np.array(v)) \
                if i < len(batches) else None
            del m, v

    @jax.jit
    def change(new, old):
        return jnp.sqrt(jnp.sum(jnp.square(new - old)))

    return {"losses": losses, "first_grad_leaf_norms": first_grad,
            "first_grad_probe": first_probe,
            "param_change_leaf_norms": np.stack([
                np.asarray(change(new, old))
                for new, old in zip(leaves, initial)])}
