"""GPT — the flagship decoder-only transformer family.

Capability target: the reference's DeepSpeed GPT trials
(examples/deepspeed/gpt_neox, BASELINE.md "DeepSpeed GPT ZeRO-2 → pjit
FSDP-style sharding") re-designed TPU-first:

 - params are a pytree with **stacked blocks** ([L, ...] leading layer dim)
   walked by ``lax.scan`` — one compiled block body regardless of depth
   (fast XLA compiles, natural pipeline-stage slicing later);
 - bf16 activations/compute, fp32 params & softmax;
 - megatron TP sharding expressed as regex→PartitionSpec rules
   (parallel/sharding.py), fsdp fallback = ZeRO-3;
 - sequence axis ready for ring attention over the ``sp`` mesh axis;
 - ``jax.checkpoint`` (remat) around each block to trade FLOPs for HBM: the
   backward pass makes a block's activations again but for the results of
   its weight products and the flash kernel's output and log-sum-exp.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from determined_clone_tpu.ops.attention import (
    causal_blockwise_attention,
    decode_attention_rows,
    mha,
    rotary_embedding,
)
from determined_clone_tpu.ops.layers import (
    dense,
    dense_init,
    dropout,
    embedding_init,
    layernorm,
    layernorm_init,
    softmax_cross_entropy,
    trunc_normal,
)
from determined_clone_tpu.models.paged import PagedModel, cast_leaves
from determined_clone_tpu.ops.moe import moe_ffn
from determined_clone_tpu.parallel.sharding import ShardingRules

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304          # gpt-neox vocab, padded to a multiple of 128
    n_layers: int = 12
    d_model: int = 768
    n_heads: int = 12
    d_ff: int = 3072
    max_seq_len: int = 2048
    dropout: float = 0.0
    compute_dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True
    # attention implementation: "auto" (flash on TPU, mha elsewhere),
    # "mha" (plain XLA), "blockwise" (streaming scan for long seqs),
    # "flash" (fused Pallas TPU kernel). TPU-first means the fused kernel
    # is the default on TPU hardware with an explicit opt-out; off-TPU the
    # kernel would run in slow interpret mode, so auto picks plain XLA.
    # The legacy blockwise_attention flag still selects "blockwise".
    attention_impl: str = "auto"
    blockwise_attention: bool = False
    # K/V block of "blockwise" only; the flash kernel sizes its own blocks
    attention_block_size: int = 512
    tie_embeddings: bool = True
    # MoE (expert parallel over the ep mesh axis; 0 = dense FFN).
    moe_experts: int = 0
    moe_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # GPipe pipeline over the pp mesh axis (0/1 = no pipelining). Takes
    # effect when apply/loss_fn receive a mesh whose pp axis is > 1.
    pipeline_microbatches: int = 0

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    @staticmethod
    def tiny() -> "GPTConfig":
        return GPTConfig(vocab_size=256, n_layers=2, d_model=64, n_heads=4,
                         d_ff=128, max_seq_len=128, remat=False)

    def paged_model(self) -> PagedModel:
        """The family on the serving path (``models/paged.py``)."""
        return PAGED


def resolved_attention_impl(cfg: GPTConfig) -> str:
    """The concrete attention kernel ``cfg`` selects on this backend.

    "auto" resolves per backend at trace time (``jax.default_backend()``
    is static under jit): the fused Pallas kernel on TPU, plain XLA
    attention elsewhere. Exposed so tests and benchmarks can assert which
    path a config actually takes — a silent fall-off the fast path is a
    perf regression, not an implementation detail.
    """
    impl = "blockwise" if cfg.blockwise_attention else cfg.attention_impl
    if impl == "auto":
        return "flash" if jax.default_backend() == "tpu" else "mha"
    if impl not in ("mha", "blockwise", "flash"):
        raise ValueError(
            f"unknown attention_impl {impl!r}; "
            f"expected auto|mha|blockwise|flash")
    return impl


# Megatron-style TP rules + explicit fsdp specs. Column-parallel up-projections
# shard the output dim on tp; row-parallel down-projections shard the input dim
# (XLA inserts the all-reduce the megatron pattern implies). Stacked block
# leaves have a leading [L] layer dim; with ``pipelined=True`` that dim is
# sliced over the pp axis (one contiguous run of layers per stage).
def sharding_rules(pipelined: bool = False) -> ShardingRules:
    lead = "pp" if pipelined else None
    return ShardingRules(rules=[
        (r"embed/table$",            P("tp", "fsdp")),       # [V, D] vocab-parallel
        (r"blocks/.*attn_qkv/kernel$",  P(lead, "fsdp", "tp")),  # [L, D, 3D] column
        (r"blocks/.*attn_out/kernel$",  P(lead, "tp", "fsdp")),  # [L, D, D]  row
        (r"blocks/.*mlp_up/kernel$",    P(lead, "fsdp", "tp")),  # [L, D, F]  column
        (r"blocks/.*mlp_down/kernel$",  P(lead, "tp", "fsdp")),  # [L, F, D]  row
        (r"blocks/moe/router/kernel$",  P(lead)),               # [L, D, E] small
        (r"blocks/moe/up/kernel$",      P(lead, "ep", "fsdp", "tp")),   # [L,E,D,F]
        (r"blocks/moe/down/kernel$",    P(lead, "ep", "tp", "fsdp")),   # [L,E,F,D]
        (r"blocks/moe/.*bias$",         P(lead, "ep")),         # [L, E, ·]
        (r"blocks/.*(bias|scale)$",     P(lead)),
        (r"lm_head/kernel$",         P("fsdp", "tp")),       # [D, V]
        (r"final_norm/",             P()),
    ])


GPT_SHARDING_RULES = sharding_rules(pipelined=False)
GPT_PP_SHARDING_RULES = sharding_rules(pipelined=True)

# Activation specs: batch over (dp, fsdp), sequence over sp, heads/features over tp.
TOKENS_SPEC = P(("dp", "fsdp"), "sp")
ACTIVATION_SPEC = P(("dp", "fsdp"), "sp", "tp")
# q/k/v as the flash kernel sees them, [B, T, H, hd]: batch and heads are
# independent, the sequence stays whole on every shard.
FLASH_QKV_SPEC = P(("dp", "fsdp"), None, "tp", None)


def init(key: jax.Array, cfg: GPTConfig) -> Params:
    """Initialize stacked-block GPT params."""
    keys = jax.random.split(key, 8)
    L, D, F = cfg.n_layers, cfg.d_model, cfg.d_ff
    dt = cfg.param_dtype

    def stacked(k, shape, stddev=0.02):
        return trunc_normal(k, (L, *shape), stddev=stddev, dtype=dt)

    blocks: Params = {
        "ln1": {"scale": jnp.ones((L, D), dt), "bias": jnp.zeros((L, D), dt)},
        "attn_qkv": {"kernel": stacked(keys[1], (D, 3 * D)),
                     "bias": jnp.zeros((L, 3 * D), dt)},
        "attn_out": {"kernel": stacked(keys[2], (D, D),
                                       stddev=0.02 / (2 * L) ** 0.5),
                     "bias": jnp.zeros((L, D), dt)},
        "ln2": {"scale": jnp.ones((L, D), dt), "bias": jnp.zeros((L, D), dt)},
    }
    if cfg.moe_experts > 0:
        from determined_clone_tpu.ops.moe import moe_init

        blocks["moe"] = jax.vmap(
            lambda k: moe_init(k, cfg.moe_experts, D, F, dtype=dt,
                               out_stddev=0.02 / (2 * L) ** 0.5)
        )(jax.random.split(keys[3], L))
    else:
        blocks["mlp_up"] = {"kernel": stacked(keys[3], (D, F)),
                            "bias": jnp.zeros((L, F), dt)}
        blocks["mlp_down"] = {"kernel": stacked(keys[4], (F, D),
                                                stddev=0.02 / (2 * L) ** 0.5),
                              "bias": jnp.zeros((L, D), dt)}
    params: Params = {
        "embed": embedding_init(keys[0], cfg.vocab_size, D, dtype=dt),
        "blocks": blocks,
        "final_norm": layernorm_init(D, dtype=dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[5], D, cfg.vocab_size, bias=False, dtype=dt)
    return params


def _flash(q: jax.Array, k: jax.Array, v: jax.Array,
           mesh: Optional[Any]) -> jax.Array:
    """Causal flash attention, each device over its own slice of the batch
    (dp, fsdp) and heads (tp) when ``mesh`` spans devices
    (``ops/flash_attention.py:flash_attention_per_shard`` says why)."""
    from determined_clone_tpu.ops.flash_attention import (
        flash_attention_per_shard,
    )

    return flash_attention_per_shard(q, k, v, mesh, FLASH_QKV_SPEC)


def _block(cfg: GPTConfig, block_params: Params, x: jax.Array,
           positions: jax.Array, dropout_key: Optional[jax.Array],
           mesh: Optional[Any] = None):
    """One pre-LN transformer block. x: [B, T, D] in compute dtype.
    ``mesh`` is only read by the flash kernel (see ``_flash``).
    Returns (x, aux) — aux is the MoE load-balancing loss (0 for dense)."""
    B, T, D = x.shape
    H, hd = cfg.n_heads, cfg.head_dim
    k_attn = k_mlp = None
    if dropout_key is not None:
        k_attn, k_mlp = jax.random.split(dropout_key)

    with jax.named_scope("attn"):
        h = layernorm(block_params["ln1"], x)
        qkv = dense(block_params["attn_qkv"], h,
                    compute_dtype=cfg.compute_dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = rotary_embedding(q.reshape(B, T, H, hd), positions)
        k = rotary_embedding(k.reshape(B, T, H, hd), positions)
        v = v.reshape(B, T, H, hd)
        impl = resolved_attention_impl(cfg)
        if impl == "blockwise":
            attn = causal_blockwise_attention(
                q, k, v, block_size=cfg.attention_block_size)
        elif impl == "flash":
            # the kernel tiles T into blocks of its own choosing; pad T to
            # the multiple it asks for (the everyday case: loss_fn slices
            # tokens[:, :-1], so 1023 -> 1024) and slice back. Safe because
            # attention is causal: real queries only ever see real keys
            # (i < T), and padded rows are discarded.
            from determined_clone_tpu.ops.flash_attention import seq_multiple

            pad = -T % seq_multiple(T)
            if pad:
                q, k, v = (jnp.pad(t, ((0, 0), (0, pad), (0, 0), (0, 0)))
                           for t in (q, k, v))
            attn = _flash(q, k, v, mesh)
            if pad:
                attn = attn[:, :T]
        else:
            attn = mha(q, k, v, causal=True)
        attn = dense(block_params["attn_out"], attn.reshape(B, T, D),
                     compute_dtype=cfg.compute_dtype)
        x = x + dropout(k_attn, attn, cfg.dropout,
                        training=k_attn is not None)

    with jax.named_scope("mlp"):
        h = layernorm(block_params["ln2"], x)
        if cfg.moe_experts > 0:
            h, aux = moe_ffn(block_params["moe"], h, k=cfg.moe_k,
                             capacity_factor=cfg.moe_capacity_factor,
                             compute_dtype=cfg.compute_dtype)
        else:
            h = dense(block_params["mlp_up"], h,
                      compute_dtype=cfg.compute_dtype)
            h = jax.nn.gelu(h, approximate=True)
            h = dense(block_params["mlp_down"], h,
                      compute_dtype=cfg.compute_dtype)
            aux = jnp.zeros((), jnp.float32)
        x = x + dropout(k_mlp, h, cfg.dropout, training=k_mlp is not None)
    return x, aux


def _forward(params: Params, cfg: GPTConfig, tokens: jax.Array, *,
             training: bool = False,
             dropout_key: Optional[jax.Array] = None,
             mesh: Optional[Any] = None):
    """Forward pass → (logits [B, T, V] fp32, aux scalar). tokens: int32 [B, T].

    Dropout is active only when ``training`` and ``dropout_key`` are given and
    ``cfg.dropout > 0``; per-layer keys are split outside the scan.

    With a mesh whose ``pp`` axis is > 1 and ``cfg.pipeline_microbatches > 1``,
    the block stack runs as a GPipe pipeline (parallel/pipeline.py): layers are
    sliced over pp, activations rotate the stage ring. (In that mode per-layer
    dropout keys are shared across microbatches — masks repeat across
    microbatches of one step; statistically harmless.)

    A mesh that spans more than one device is also what lets the flash
    kernel run sharded (``_flash``): a jitted step over such a mesh must
    pass it, or the TPU compiler refuses the unpartitionable kernel.
    """
    B, T = tokens.shape
    positions = jnp.arange(T)
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"]["table"], tokens,
                     axis=0).astype(cfg.compute_dtype)

    use_dropout = training and dropout_key is not None and cfg.dropout > 0.0
    layer_keys = (
        jax.random.split(dropout_key, cfg.n_layers) if use_dropout else None
    )

    pp = mesh.shape.get("pp", 1) if mesh is not None else 1
    pipelined = pp > 1 and cfg.pipeline_microbatches > 1

    def block_fn(layer_params, x, key):
        # pipeline stages already run inside pipeline_apply's shard_map
        return _block(cfg, layer_params, x, positions, key,
                      None if pipelined else mesh)
    if cfg.remat:
        from determined_clone_tpu.ops.flash_attention import (
            save_flash_residuals,
        )

        # the block's products, and the flash kernel's output and
        # log-sum-exp (a custom call, not a dot): the backward pass reads
        # them and does not run the kernel's forward again
        policies = jax.checkpoint_policies
        block_fn = jax.checkpoint(
            block_fn, policy=policies.save_from_both_policies(
                policies.dots_with_no_batch_dims_saveable,
                save_flash_residuals))

    if pipelined:
        from determined_clone_tpu.parallel.pipeline import pipeline_apply

        M = cfg.pipeline_microbatches
        stacked: Params = {"blocks": params["blocks"]}
        if layer_keys is not None:
            stacked["keys"] = layer_keys

        def stage_fn(local, carrier):
            has_keys = "keys" in local
            xs = (local["blocks"], local["keys"]) if has_keys else local["blocks"]

            def body(carry, inp):
                h, aux = carry
                lp, key = inp if has_keys else (inp, None)
                h, a = block_fn(lp, h, key)
                # Spread the scalar aux over the microbatch's batch rows so the
                # carrier keeps its [mb] shape; summing recovers the total.
                return (h, aux + a / h.shape[0]), None

            (h, aux), _ = jax.lax.scan(body, (carrier["x"], carrier["aux"]), xs)
            return {"x": h, "aux": aux}

        carrier = {"x": x, "aux": jnp.zeros((B,), jnp.float32)}
        out = pipeline_apply(stage_fn, stacked, carrier, mesh=mesh,
                             num_microbatches=M)
        x = out["x"]
        aux_total = jnp.sum(out["aux"]) / M  # mean over microbatches
    elif layer_keys is not None:
        def scan_body(x, inputs):
            layer_params, key = inputs
            x, aux = block_fn(layer_params, x, key)
            return x, aux
        x, aux_stack = jax.lax.scan(scan_body, x, (params["blocks"], layer_keys))
        aux_total = jnp.sum(aux_stack)
    else:
        def scan_body(x, layer_params):
            x, aux = block_fn(layer_params, x, None)
            return x, aux
        x, aux_stack = jax.lax.scan(scan_body, x, params["blocks"])
        aux_total = jnp.sum(aux_stack)

    with jax.named_scope("logits"):
        x = layernorm(params["final_norm"], x)
        if cfg.tie_embeddings:
            logits = (x.astype(jnp.float32)
                      @ params["embed"]["table"].astype(jnp.float32).T)
        else:
            logits = dense(params["lm_head"], x, compute_dtype=jnp.float32)
        logits = logits.astype(jnp.float32)
    return logits, aux_total


def apply(params: Params, cfg: GPTConfig, tokens: jax.Array, *,
          training: bool = False,
          dropout_key: Optional[jax.Array] = None,
          mesh: Optional[Any] = None) -> jax.Array:
    """Forward pass → logits [B, T, V] (fp32); see ``_forward``."""
    logits, _ = _forward(params, cfg, tokens, training=training,
                         dropout_key=dropout_key, mesh=mesh)
    return logits


def loss_fn(params: Params, cfg: GPTConfig, tokens: jax.Array,
            targets: jax.Array, mask: Optional[jax.Array] = None, *,
            training: bool = False,
            dropout_key: Optional[jax.Array] = None,
            mesh: Optional[Any] = None) -> jax.Array:
    """Mean next-token cross-entropy (+ MoE aux loss). targets/mask: [B, T]."""
    logits, aux = _forward(params, cfg, tokens, training=training,
                           dropout_key=dropout_key, mesh=mesh)
    with jax.named_scope("logits"):
        per_tok = softmax_cross_entropy(logits, targets)
        if mask is not None:
            maskf = mask.astype(jnp.float32)
            ce = jnp.sum(per_tok * maskf) / jnp.maximum(jnp.sum(maskf), 1.0)
        else:
            ce = jnp.mean(per_tok)
    if cfg.moe_experts > 0:
        ce = ce + cfg.moe_aux_weight * aux
    return ce


def _block_paged(cfg: GPTConfig, block_params: Params, x: jax.Array,
                 positions: jax.Array, k_rows: jax.Array,
                 v_rows: jax.Array, scatter_idx: jax.Array,
                 gather_blocks: jax.Array, attn_mask: jax.Array,
                 lengths: Optional[jax.Array]):
    """One pre-LN block on the paged-KV serving path.

    x: [B, T, D] new tokens only (prefill: the prompt; decode: T=1).
    k_rows/v_rows: [L*N*bs, R] — the whole paged pool as rows, one row
    per (layer, slot), R >= D (see serving/kv_cache.py:kv_row_width).
    The new tokens' K/V rows are scattered into it at ``scatter_idx``
    ([B*T] row ids of this layer, out-of-range = padding → dropped),
    then attention gathers the full paged context back, a block of rows
    at a time, via ``gather_blocks`` ([B, W] block ids of this layer)
    under ``attn_mask`` ([B, 1, T, S], S = W * bs). Two sequences never
    share a pool block, so the scatter indices are collision-free by
    construction. Where ``lengths`` ([B], the positions each row attends)
    is given, which is a decode step on the kernel's path
    (``_paged_backbone``), nothing is gathered: the kernel of
    ``ops/paged_attention.py`` reads the pool through ``gather_blocks``
    at each row's own length.

    Returns (x, k_rows, v_rows) — the same block math as ``_block``
    (dense or MoE FFN), minus dropout (inference) and remat.
    """
    B, T, D = x.shape
    H, hd = cfg.n_heads, cfg.head_dim
    S, R = attn_mask.shape[-1], k_rows.shape[1]
    bs = S // gather_blocks.shape[1]
    pad = ((0, 0), (0, R - D))  # columns D..R stay zero

    with jax.named_scope("attn"):
        h = layernorm(block_params["ln1"], x)
        qkv = dense(block_params["attn_qkv"], h,
                    compute_dtype=cfg.compute_dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = rotary_embedding(q.reshape(B, T, H, hd), positions)
        k = rotary_embedding(k.reshape(B, T, H, hd), positions)

        with jax.named_scope("kv_cache"):
            k_rows = k_rows.at[scatter_idx].set(
                jnp.pad(k.reshape(B * T, D), pad), mode="drop")
            v_rows = v_rows.at[scatter_idx].set(
                jnp.pad(v.reshape(B * T, D), pad), mode="drop")
            if lengths is None:
                # gather the whole paged context: [B, S, R]; slot j of the
                # gathered context is sequence position j (block tables map
                # contiguously). By block, not by row: the same bytes in
                # bs-times fewer, bs-times longer pieces (3.5 x faster on
                # v5e)
                ctx_k = k_rows.reshape(-1, bs, R)[gather_blocks].reshape(
                    B, S, R)
                ctx_v = v_rows.reshape(-1, bs, R)[gather_blocks].reshape(
                    B, S, R)
        if lengths is not None:  # decode, through the table
            from determined_clone_tpu.ops import paged_attention as paged

            with jax.named_scope("paged_attn"):
                attn = paged.paged_attention(
                    q, k_rows.reshape(-1, bs, R), v_rows.reshape(-1, bs, R),
                    gather_blocks, lengths)
        elif T == 1:  # decode: read the gathered rows as they lie
            attn = decode_attention_rows(q, ctx_k, ctx_v, attn_mask)
        else:
            attn = mha(q, ctx_k[..., :D].reshape(B, S, H, hd),
                       ctx_v[..., :D].reshape(B, S, H, hd), causal=False,
                       mask=attn_mask)
        attn = dense(block_params["attn_out"], attn.reshape(B, T, D),
                     compute_dtype=cfg.compute_dtype)
        x = x + attn

    with jax.named_scope("mlp"):
        h = layernorm(block_params["ln2"], x)
        if cfg.moe_experts > 0:
            h, _ = moe_ffn(block_params["moe"], h, k=cfg.moe_k,
                           capacity_factor=cfg.moe_capacity_factor,
                           compute_dtype=cfg.compute_dtype)
        else:
            h = dense(block_params["mlp_up"], h,
                      compute_dtype=cfg.compute_dtype)
            h = jax.nn.gelu(h, approximate=True)
            h = dense(block_params["mlp_down"], h,
                      compute_dtype=cfg.compute_dtype)
        x = x + h
    return x, k_rows, v_rows


def _paged_backbone(params: Params, cfg: GPTConfig, tokens: jax.Array,
                    positions: jax.Array, token_mask: jax.Array,
                    k_pool: jax.Array, v_pool: jax.Array,
                    block_tables: jax.Array):
    """Embed → paged transformer stack → final layernorm.

    The shared core of :func:`forward_paged` (last-token readout, the
    prefill/decode workhorse) and :func:`forward_paged_logits` (all-token
    readout, the speculative-verify workhorse). Returns
    ``(x [B, T, D] normed, k_pool, v_pool)``.

    The pools are carried through the layer scan as rows ``[L*N*bs, R]``
    (a bitcast of ``[L, N, bs, R]``) and each layer scatters and gathers
    at its own offset, so a donated pool is updated in place: no
    per-layer slice is taken out and stacked back.

    A decode step (``T == 1``) gathers nothing where the attention kernels
    are on (``resolved_attention_impl``: the chip, or ``"flash"``) and the
    paged kernel can take the pool's shapes: it reads each row's context
    through the table, ``positions + 1`` rows of a live row and none of a
    padding row. ``T > 1`` gathers whole tables and runs ``mha``.
    """
    B, T = tokens.shape
    L, N, bs, R = k_pool.shape
    W = block_tables.shape[1]
    S = W * bs

    # scatter slots for the new tokens: pool block backing position p is
    # block_tables[b, p // bs]; padding tokens get a row past the last
    # layer's (and stay there under any layer's offset) so
    # .at[].set(mode="drop") discards them
    blk = jnp.take_along_axis(block_tables, positions // bs, axis=1)
    scatter_idx = jnp.where(token_mask, blk * bs + positions % bs,
                            L * N * bs).reshape(B * T)
    # context slot j == sequence position j: causal = "j <= my position"
    attn_mask = (jnp.arange(S)[None, None, :] <= positions[:, :, None]
                 ) & token_mask[:, :, None]
    attn_mask = attn_mask[:, None]  # [B, 1, T, S] broadcast over heads
    lengths = None
    if T == 1 and resolved_attention_impl(cfg) == "flash":
        # imported where a program needs it, as _block imports the
        # training kernels: Pallas costs a second of a process's start
        from determined_clone_tpu.ops import paged_attention as paged

        if paged.fits(W, bs, cfg.n_heads, R, cfg.compute_dtype):
            lengths = jnp.where(token_mask[:, 0], positions[:, 0] + 1, 0)

    with jax.named_scope("embed"):
        x = jnp.take(params["embed"]["table"], tokens,
                     axis=0).astype(cfg.compute_dtype)

    def scan_body(carry, layer_in):
        x, k_rows, v_rows = carry
        layer_params, first_block = layer_in
        x, k_rows, v_rows = _block_paged(
            cfg, layer_params, x, positions, k_rows, v_rows,
            first_block * bs + scatter_idx, first_block + block_tables,
            attn_mask, lengths)
        return (x, k_rows, v_rows), None

    (x, k_rows, v_rows), _ = jax.lax.scan(
        scan_body,
        (x, k_pool.reshape(L * N * bs, R), v_pool.reshape(L * N * bs, R)),
        (params["blocks"], jnp.arange(L, dtype=jnp.int32) * N))

    with jax.named_scope("logits"):
        x = layernorm(params["final_norm"], x)
    return (x, k_rows.reshape(L, N, bs, R), v_rows.reshape(L, N, bs, R))


def forward_paged(params: Params, cfg: GPTConfig, tokens: jax.Array,
                  positions: jax.Array, token_mask: jax.Array,
                  last_index: jax.Array, k_pool: jax.Array,
                  v_pool: jax.Array, block_tables: jax.Array):
    """KV-cache-aware forward for online serving (paged attention).

    ONE function covers both halves of the prefill/decode split — the
    serving engine jits it once and XLA compiles one program per
    (batch-bucket, length-bucket) shape:

    - **prefill**: ``tokens`` is the bucketed-padded prompt ([B, T]),
      every prompt token's K/V is written into the pool, and the returned
      logits are each row's *last real token* (→ first sampled token);
    - **decode**: ``T == 1`` — one new token per running sequence is
      appended to the pool and attends to its full paged context.

    Args:
      tokens:     int32 [B, T] new token ids.
      positions:  int32 [B, T] absolute sequence positions of ``tokens``.
      token_mask: bool  [B, T] — False marks batch/length padding; padded
                  tokens are neither written to the pool nor attended to.
      last_index: int32 [B] — index into T of each row's last real token
                  (prefill: prompt_len-1; decode: 0).
      k_pool/v_pool: [L, N, block, R] paged pools, one row per position
                  with all heads side by side, ``R >= H * hd`` a multiple
                  of 128 (serving/kv_cache.py:init_kv_pools says why).
                  Callers jitting this should donate both: the pool is
                  then updated in place, and leaves in the shape it came.
      block_tables: int32 [B, W] pool block ids per sequence; entry w
                  backs sequence positions [w*block, (w+1)*block). Padding
                  entries may hold any valid id — they are never written
                  (mask) and reads of them are masked out of attention.

    Returns ``(logits [B, V] fp32, k_pool, v_pool)``.

    Numerics match :func:`apply` (same dtypes, fp32 softmax/logits): a
    greedy decode through this path is token-identical to re-running the
    full uncached forward each step — tests/test_serving.py asserts it.
    """
    x, k_pool, v_pool = _paged_backbone(params, cfg, tokens, positions,
                                        token_mask, k_pool, v_pool,
                                        block_tables)
    with jax.named_scope("logits"):
        h_last = jnp.take_along_axis(
            x, last_index[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        if cfg.tie_embeddings:
            logits = (h_last.astype(jnp.float32)
                      @ params["embed"]["table"].astype(jnp.float32).T)
        else:
            logits = dense(params["lm_head"], h_last,
                           compute_dtype=jnp.float32)
        logits = logits.astype(jnp.float32)
    return logits, k_pool, v_pool


def forward_paged_logits(params: Params, cfg: GPTConfig, tokens: jax.Array,
                         positions: jax.Array, token_mask: jax.Array,
                         k_pool: jax.Array, v_pool: jax.Array,
                         block_tables: jax.Array):
    """Multi-token paged forward returning logits at *every* position.

    The speculative-decoding verify step (docs/serving.md): the target
    model scores ``[last committed token, draft_1 .. draft_k]`` in one
    call — ``T == k + 1`` — and the engine accepts the longest draft
    prefix whose tokens equal the target's own greedy picks. Because the
    logits at position i condition only on real committed/accepted
    context (the accept rule stops at the first disagreement), greedy
    output is bit-identical to one-token-at-a-time decode for any draft.

    Same argument contract as :func:`forward_paged` minus ``last_index``;
    returns ``(logits [B, T, V] fp32, k_pool, v_pool)``. K/V for all
    masked-in tokens are written to the pool — rejected drafts leave
    stale entries past the accepted frontier, which is safe: attention
    masks slots beyond the query's own position, and the next iteration's
    scatter overwrites them before they ever become visible.
    """
    x, k_pool, v_pool = _paged_backbone(params, cfg, tokens, positions,
                                        token_mask, k_pool, v_pool,
                                        block_tables)
    with jax.named_scope("logits"):
        if cfg.tie_embeddings:
            logits = (x.astype(jnp.float32)
                      @ params["embed"]["table"].astype(jnp.float32).T)
        else:
            logits = dense(params["lm_head"], x, compute_dtype=jnp.float32)
        logits = logits.astype(jnp.float32)
    return logits, k_pool, v_pool


def _cache_layout(cfg: GPTConfig, cache: Any) -> Any:
    # imported here: serving/ imports this module at import time
    from determined_clone_tpu.serving.kv_cache import CacheLayout

    return CacheLayout(cache, cfg.max_seq_len)


# what _block_paged hands to dense / moe_ffn with compute_dtype=: kernel
# and bias alike (dense adds the bias in the product's type)
_BLOCK_MATRICES = re.compile(
    r"^blocks/(attn_qkv|attn_out|mlp_up|mlp_down|moe/(up|down))/")


def serving_params(params: Params, cfg: GPTConfig) -> Params:
    """The tree :func:`forward_paged` reads without converting a leaf: the
    block matrices in ``cfg.compute_dtype``, rounded once, as ``dense``
    would round them in every call (the same bits). The norms, the MoE
    router, the table and the head stay as they are: the norms compute
    in float32 and the logits multiply the table in float32."""
    return cast_leaves(
        params,
        lambda path: cfg.compute_dtype if _BLOCK_MATRICES.match(path)
        else None)


PAGED = PagedModel(family="gpt", forward_paged=forward_paged,
                   forward_paged_logits=forward_paged_logits,
                   init=init,
                   cache_layout=_cache_layout,
                   serving_params=serving_params,
                   row_counters=("serving_kv_rows_attended_total",
                                 "serving_kv_rows_tabled_total"))


def param_count(params: Params) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))


def extend_with_identity_layers(params: Params, cfg: GPTConfig,
                                extra_layers: int):
    """Append ``extra_layers`` exact-identity residual blocks.

    Pre-LN blocks add their output to the residual stream, so a block
    whose ``attn_out`` and ``mlp_down`` projections (kernel AND bias)
    are zero contributes exactly zero: the extended model's logits are
    bit-identical to the original's, while every call pays the deeper
    model's weight traffic and op count (the QKV/up projections and
    attention still run — only the final adds vanish). That makes the
    pair (original, extended) a controlled speculative-decoding
    testbed: the original IS a perfectly-distilled draft of the
    extended target, so greedy acceptance is exactly 1.0.
    tests/test_serving_speed.py uses it to test speculative decoding
    without training a real draft.

    Returns ``(params, cfg)`` for the deepened model. Stacked-block
    layout means extension is a leading-axis concat; MoE blocks are not
    supported (no per-expert identity construction).
    """
    if extra_layers <= 0:
        return params, cfg
    if cfg.moe_experts > 0:
        raise ValueError("identity extension supports dense blocks only")

    zero_adds = ("attn_out", "mlp_down")

    def pad(path_top: str, leaf: jax.Array) -> jax.Array:
        tile = jnp.tile(leaf[:1], (extra_layers,) + (1,) * (leaf.ndim - 1))
        if path_top in zero_adds:
            tile = jnp.zeros_like(tile)
        return jnp.concatenate([leaf, tile], axis=0)

    blocks = {name: {k: pad(name, v) for k, v in sub.items()}
              for name, sub in params["blocks"].items()}
    out = dict(params)
    out["blocks"] = blocks
    return out, dataclasses.replace(
        cfg, n_layers=cfg.n_layers + extra_layers)


def slice_prefix_layers(params: Params, cfg: GPTConfig, n_layers: int):
    """Keep only the first ``n_layers`` stacked blocks (embed, final
    norm and head shared) — the draft half of the identity-extension
    testbed, and the cheap way to carve a layer-sliced draft out of any
    stacked-block checkpoint. Returns ``(params, cfg)``."""
    if not 0 < n_layers <= cfg.n_layers:
        raise ValueError(f"n_layers must be in [1, {cfg.n_layers}], "
                         f"got {n_layers}")
    blocks = {name: {k: v[:n_layers] for k, v in sub.items()}
              for name, sub in params["blocks"].items()}
    out = dict(params)
    out["blocks"] = blocks
    return out, dataclasses.replace(cfg, n_layers=n_layers)
