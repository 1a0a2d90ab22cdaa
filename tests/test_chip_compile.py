"""What the chip's compiler says, asked without the chip — and chip_smoke.py
rehearsed on the CPU.

The TPU compiler is installed wherever JAX is; it compiles for a v5e that
is *described* (``topologies.get_topology_desc``), not attached. These
tests compile the main path's kernels and programs at GPT-2-small width,
so that a kernel the chip would refuse (tiling, VMEM, partitioning) fails
here, at no chip time. A compile that passes is not a chip run: nothing
executes, nothing is timed. Skipped where the topology cannot be described.

Code that asks ``jax.default_backend()`` sees the CPU under such a compile
and would take its CPU branch, so the tests steer it themselves
(``attention_impl="flash"``, ``interpret=False`` / monkeypatch).
"""
import functools
import math
import os
import random
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

from determined_clone_tpu.models import gpt  # noqa: E402
from determined_clone_tpu.ops import (  # noqa: E402
    eva_paged_attention as eva_paged_mod,
)
from determined_clone_tpu.ops import flash_attention as flash_mod  # noqa: E402
from determined_clone_tpu.ops import paged_attention as paged_mod  # noqa: E402
from determined_clone_tpu.parallel import MeshSpec, make_mesh  # noqa: E402
from determined_clone_tpu.utils import compile_cache  # noqa: E402

GPT2_SMALL = gpt.GPTConfig(max_seq_len=1024)  # serving's view of fsdp.yaml
V5E_HBM_BYTES = 16e9
# what the chip's allocator offers a process (``memory_stats()["bytes_limit"]``
# of a v5e, read on the chip by PR 44): 15.75 GiB
V5E_BYTES_LIMIT = 16909336064


@pytest.fixture(scope="module")
def v5e():
    """The four described chips of a v5e 2x2 host."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "not here"
        pytest.skip(f"cannot describe a v5e topology here: {e!r}")
    # such a compile is written to the persistent cache but cannot be read
    # back without a chip (the next one would warn and compile again)
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield list(topo.devices)
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def test_described_chip_is_in_the_peak_table(v5e):
    """The device_kind the smoke will meet has a published peak."""
    from determined_clone_tpu.telemetry import flops

    kind = v5e[0].device_kind
    assert flops.peak_flops_estimate("tpu", kind) == (197e12, "tpu:v5e")
    assert flops.TPU_HBM_BYTES_PER_S[flops.TPU_DEVICE_KINDS[kind]] == 819e9


def _flash_calls(hlo: str, bare: bool = True):
    """The flash kernels among the Mosaic custom calls of an optimised HLO
    text: their kernel names (``bare``; a grad taken outside a scan
    prefixes the instruction with its transform, ``jvp_flash_fwd_``) or the
    instructions' names as a device trace shows them."""
    names = re.findall(r"%([\w.]+) = [^\n]*custom-call\([^\n]*"
                       r'custom_call_target="tpu_custom_call"', hlo)
    if not bare:
        return names
    return [re.search(r"flash_(fwd|bwd_dkv|bwd_dq)", n).group(0)
            for n in names]


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
def test_flash_kernel_compiles_at_gpt2_width(v5e, grad):
    one = SingleDeviceSharding(v5e[0])
    x = jax.ShapeDtypeStruct((8, 1024, 12, 64), jnp.bfloat16, sharding=one)
    fn = functools.partial(flash_mod.flash_attention, causal=True,
                           interpret=False)
    if grad:
        fn = jax.grad(lambda q, k, v, f=fn: f(q, k, v).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))
    calls = _flash_calls(jax.jit(fn).lower(x, x, x).compile().as_text())
    # the backward pass is Pallas too and needs the forward's output and
    # log-sum-exp, so a bare grad keeps the forward kernel alive
    assert sorted(calls) == (["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
                             if grad else ["flash_fwd"])


def test_flash_kernel_is_named_in_the_compiled_program(v5e):
    """The custom call is ``flash_fwd`` in the optimised HLO, which is the
    name its events carry in a device trace (PERF.md section 3)."""
    one = SingleDeviceSharding(v5e[0])
    x = jax.ShapeDtypeStruct((8, 1024, 12, 64), jnp.bfloat16, sharding=one)
    fn = functools.partial(flash_mod.flash_attention, causal=True,
                           interpret=False)
    text = jax.jit(fn).lower(x, x, x).compile().as_text()
    assert _flash_calls(text) == ["flash_fwd"]


@pytest.mark.parametrize("shape", [(8, 1024, 16, 64), (2, 1024, 25, 64)],
                         ids=["gpt2-medium", "gpt2-xl-per-chip"])
def test_flash_grad_at_the_train_cells_shapes(v5e, shape):
    """``grad`` at the two train cells' per-chip shapes, bf16: forward and
    backward are Mosaic kernels, no score-shaped block (``[.., 1024, 128]``
    and wider, or the XLA scan's stacked ``[8, 8, ...]``) reaches HBM, and
    q/k/v enter the kernels as bf16, never converted to f32."""
    one = SingleDeviceSharding(v5e[0])
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one)
    fn = jax.grad(lambda q, k, v: flash_mod.flash_attention(
        q, k, v, causal=True, interpret=False).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))
    text = jax.jit(fn).lower(x, x, x).compile().as_text()
    assert sorted(_flash_calls(text)) == [
        "flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
    assert " while(" not in text
    B, T, H, D = shape
    for dims in re.findall(r"(?:f32|bf16)\[([\d,]+)\]", text):
        dims = [int(n) for n in dims.split(",")]
        # nothing larger than an operand: a score block would be T x 128+
        assert math.prod(dims) <= B * T * H * D, dims
        assert dims[-2:] != [T, 128] and dims[:2] != [8, 8], dims
    # the kernels' tensor operands are bf16 as they arrived and as they
    # lie: medium's 16 heads two a lane block, xl's 25 as twelve pairs and
    # one alone in a block that hangs over the edge
    assert flash_mod.layout(H, D) == (True, 2, -(-H // 2))
    operand = f"[{B},{T},{H * D}]"
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            operands = line.split("operand_layout_constraints={")[1]
            operands = operands.split("}, frontend_attributes")[0]
            assert "f32" + operand not in operands, operands
            assert operands.count("bf16" + operand) >= 3, operands


def test_flash_grad_at_the_expert_cells_shape(v5e):
    """``grad`` at ``glm-4.7-flash.train-8k``'s shape, one row of 8192
    positions and 20 heads of 256, bf16: three Mosaic kernels, one head a
    grid step as it lies, bf16 operands never converted, no score-shaped
    block in HBM, and blocks whose fast memory stays within what the
    kernels ask for."""
    shape = (1, 8192, 20, 256)
    one = SingleDeviceSharding(v5e[0])
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one)
    fn = jax.grad(lambda q, k, v: flash_mod.flash_attention(
        q, k, v, causal=True, interpret=False).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))
    text = jax.jit(fn).lower(x, x, x).compile().as_text()
    assert sorted(_flash_calls(text)) == [
        "flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
    B, T, H, D = shape
    for dims in re.findall(r"(?:f32|bf16)\[([\d,]+)\]", text):
        dims = [int(n) for n in dims.split(",")]
        assert math.prod(dims) <= B * T * H * D, dims
        assert dims[-2:] != [T, T], dims
    assert flash_mod.layout(H, D) == (True, 1, H)
    operand = f"[{B},{T},{H * D}]"
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            operands = line.split("operand_layout_constraints={")[1]
            operands = operands.split("}, frontend_attributes")[0]
            assert "f32" + operand not in operands, operands
            assert operands.count("bf16" + operand) >= 3, operands
    blocks = flash_mod.block_sizes(T, T, D, jnp.bfloat16)
    assert blocks.fwd == (1024, 1024, 1024)
    for block in blocks:
        tq, tk = block.tiles
        used = 2 * 2 * (block.q + block.k) * D * 2 \
            + 2 * max(block.q, block.k) * D * 4 + 4 * tq * tk * 4
        assert used <= flash_mod._VMEM_LIMIT, (block, used)


def test_expert_cells_train_step_compiles_and_fits(v5e, monkeypatch):
    """``glm-4.7-flash.train-8k``'s step as the trainer builds it (the
    example trial's loss and ``apply_statistics``, clip + AdamW with the
    bias masked, state donated) at ``[1, 8193]`` tokens for one described
    v5e: the compiler's memory analysis is under the 15.75 GiB the chip's
    allocator offers; the three flash kernels are custom calls at head
    size 256 on the ``mla_attn`` path, each three times (the dense run,
    the expert run, the prediction module: a layer's remat keeps the
    forward's output and log-sum-exp) and the held experts' grouped products
    (``grouped_matmul``, ``grouped_outer`` for the weights' gradients and
    ``grouped_add_rows`` for the sums into the tokens' rows:
    ``ops/grouped_matmul.py``) on the ``moe_experts`` path, no
    other kernel anywhere; nothing is shaped by all the pairs (``[32768,
    2048]``), by experts x capacity (``[8192, 64, C]``) or by positions
    squared (``f32[8192, 8192]``).

    80 s, and in tier-1 all the same: the analysis reads 15.95e9 where the
    chip's own peak is 13.98e9 (PR 44), so it stands 1 GB under the limit
    and a later change's GB decides whether the cell runs."""
    import json

    import optax

    from benchmarks.adapters import glm4_moe_lite as adapter
    from determined_clone_tpu.models import glm_moe_lite
    from determined_clone_tpu.training.train_step import (
        create_train_state,
        make_train_step,
    )

    from determined_clone_tpu.ops import grouped_matmul as grouped_mod

    monkeypatch.setattr(flash_mod, "_should_interpret", lambda: False)
    monkeypatch.setattr(grouped_mod, "_should_interpret", lambda: False)
    one = SingleDeviceSharding(v5e[0])
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "glm-4.7-flash.json")) as f:
        config = json.load(f)
    cfg = adapter.model_config(config)
    opt = config["training"]["optimizer"]
    tx = optax.chain(
        optax.clip_by_global_norm(opt["clip_global_norm"]),
        optax.adamw(opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                    weight_decay=opt["weight_decay"],
                    mask=glm_moe_lite.trained_mask))
    state = _shapes(jax.eval_shape(lambda: create_train_state(
        adapter._weights(jax.random.PRNGKey(0), adapter.dims(config)), tx,
        jax.random.PRNGKey(1))), one)
    batch = jax.ShapeDtypeStruct((1, 8193), jnp.int32, sharding=one)
    step = make_train_step(
        lambda p, b, r: glm_moe_lite.loss_fn(p, cfg, b[:, :-1], b[:, 1:]),
        tx, apply_statistics=lambda p, s:
        glm_moe_lite.update_selection_bias(p, cfg, s))
    compiled = step.lower(state, batch).compile()
    mem = compiled.memory_analysis()
    peak = mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + mem.output_size_in_bytes - mem.alias_size_in_bytes
    assert 0.25 * 16e9 < peak < V5E_BYTES_LIMIT, peak
    text = compiled.as_text()
    names = [n.split(".")[0] for n in _flash_calls(text, bare=False)]
    assert set(names) == {
        "flash_fwd", "flash_bwd_dkv", "flash_bwd_dq",
        "grouped_matmul", "grouped_outer", "grouped_add_rows"}
    # the dense run, the expert run and the prediction module: each runs
    # the forward kernel once, its output and log-sum-exp kept across remat
    assert [names.count(k) for k in (
        "flash_fwd", "flash_bwd_dkv", "flash_bwd_dq")] == [3, 3, 3], names
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            name = re.search(r"%([\w.]+) = ", line).group(1).split(".")[0]
            path = re.search(r'op_name="([^"]+)"', line).group(1)
            if name.startswith("grouped_"):
                assert "/moe_experts/" in path, path
            else:
                assert "/mla_attn/" in path and "[1,8192,5120]" in line, path
    for dims in re.findall(r"(?:f32|bf16|s32|pred)\[([\d,]+)\]", text):
        dims = [int(n) for n in dims.split(",")]
        assert dims[:2] != [32768, 2048], dims
        assert dims[:2] != [8192, 64] or len(dims) == 2, dims
        assert dims[-2:] != [8192, 8192], dims


@pytest.mark.parametrize("fsdp", [1, 4])
def test_train_step_names_its_kernels_as_the_benchmark_reads_them(
        v5e, monkeypatch, fsdp):
    """In a scanned, rematerialised train step the instructions are
    ``flash_fwd.N``, ``flash_bwd_dkv.N`` and ``flash_bwd_dq.N``, one of
    each: the block's remat policy keeps the forward's output and
    log-sum-exp (``flash_attention.save_flash_residuals``), so the
    backward pass does not run the forward again, on one chip or inside
    the ``shard_map`` of an ``fsdp`` mesh. The benchmark's
    ``flash_fwd_device_ms`` takes every operation whose name starts with
    ``flash_fwd`` (benchmarks/harness/scopes.py), so the backward's must
    not, and all of them lie under the ``attn`` scope."""
    from jax.sharding import NamedSharding

    monkeypatch.setattr(flash_mod, "_should_interpret", lambda: False)
    cfg = gpt.GPTConfig(vocab_size=512, n_layers=2, d_model=256, n_heads=4,
                        d_ff=512, max_seq_len=1024, remat=True,
                        attention_impl="flash")
    shapes = jax.eval_shape(lambda: gpt.init(jax.random.PRNGKey(0), cfg))
    mesh = make_mesh(MeshSpec(fsdp=fsdp), v5e[:fsdp])
    params = jax.tree.map(
        lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
        shapes, gpt.GPT_SHARDING_RULES.shardings_for(shapes, mesh))
    tokens = jax.ShapeDtypeStruct(
        (4, 1023), jnp.int32, sharding=NamedSharding(mesh, gpt.TOKENS_SPEC))
    grad = jax.grad(lambda p, t: gpt.loss_fn(p, cfg, t, t, mesh=mesh))
    text = jax.jit(grad).lower(params, tokens).compile().as_text()
    names = sorted(n.split(".")[0] for n in _flash_calls(text, bare=False))
    assert names == ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"], names
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            path = re.search(r'op_name="([^"]+)"', line).group(1)
            assert re.search(r"/attn/(shard_map/)?flash_", path), path


def test_flash_kernel_runs_per_shard_under_a_mesh(v5e, monkeypatch):
    """XLA refuses to partition a Mosaic kernel; gpt._flash shard_maps it
    over batch (dp, fsdp) and heads (tp) — the fault the four-chip compile
    found before any chip time was spent. The backward kernels run inside
    the same ``shard_map``'s transpose, per shard."""
    from jax.sharding import NamedSharding

    monkeypatch.setattr(flash_mod, "_should_interpret", lambda: False)
    mesh = make_mesh(MeshSpec(fsdp=2, tp=2), v5e)
    x = jax.ShapeDtypeStruct((8, 1024, 12, 64), jnp.bfloat16,
                             sharding=NamedSharding(mesh, gpt.FLASH_QKV_SPEC))
    with pytest.raises(NotImplementedError, match="shard_map"):
        jax.jit(lambda q, k, v: gpt._flash(q, k, v, None)
                ).lower(x, x, x).compile()
    compiled = jax.jit(lambda q, k, v: gpt._flash(q, k, v, mesh)
                       ).lower(x, x, x).compile()
    assert _flash_calls(compiled.as_text()) == ["flash_fwd"]
    grad = jax.grad(lambda q, k, v: gpt._flash(q, k, v, mesh).astype(
        jnp.float32).sum(), argnums=(0, 1, 2))
    text = jax.jit(grad).lower(x, x, x).compile().as_text()
    assert sorted(_flash_calls(text)) == [
        "flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
    # each device's kernels see its own shard: 8 / 2 rows x 12 / 2 heads,
    # read in place
    assert "bf16[4,1024,384]" in text and "bf16[8,1024,768]" not in text


def _step_inputs(arr, batch, t, table_width):
    """What the engine's jitted forward takes between the model config
    and the pools (``serving/engine.py:forward_paged``): a row's ``t``
    tokens with its first position, its count and ``src``; the block
    table; the last call's sampled tokens, as wide as the largest batch
    bucket (here: this batch)."""
    return (arr((batch, t + 3), jnp.int32),
            arr((batch, table_width), jnp.int32), arr((batch,), jnp.int32))


def _compile_paged_forward(cfg, device, *, blocks, block, batch, t):
    """The engine's jitted forward for a described chip, the weights in
    the form the engine holds them in (``serving_params``), pools as
    ``init_kv_pools`` shapes them. Returns (compiled, one pool's shape)."""
    from determined_clone_tpu.serving.engine import make_paged_forward
    from determined_clone_tpu.serving.kv_cache import (
        KVCacheConfig,
        init_kv_pools,
    )

    one = SingleDeviceSharding(device)
    params = jax.eval_shape(
        lambda k: gpt.serving_params(gpt.init(k, cfg), cfg),
        jax.random.PRNGKey(0))
    k_pool, v_pool = _shapes(jax.eval_shape(
        lambda: init_kv_pools(cfg, KVCacheConfig(blocks, block))), one)

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    compiled = make_paged_forward().lower(
        _shapes(params, one), cfg,
        *_step_inputs(arr, batch, t, cfg.max_seq_len // block),
        k_pool, v_pool).compile()
    return compiled, k_pool.shape


@pytest.mark.parametrize("batch,t", [(8, 1), (8, 128)],
                         ids=["decode", "prefill"])
def test_paged_forward_compiles_at_gpt2_small(v5e, batch, t):
    """The engine's one jitted entry point, at the shapes its default
    ServingConfig warms up (pool of 512 blocks x 16 positions)."""
    compiled, _ = _compile_paged_forward(GPT2_SMALL, v5e[0], blocks=512,
                                         block=16, batch=batch, t=t)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM_BYTES


_COPY_OPCODES = ("copy", "copy-start", "copy-done", "dynamic-slice",
                 "dynamic-update-slice", "slice", "transpose")
_HLO_LINE = re.compile(
    r"^\s*(?:ROOT )?%?(?P<name>[\w.\-]+) = (?P<result>\(?\w+\[[^=]*?) "
    r"(?P<opcode>[\w\-]+)\(")


def _pool_sized_copies(hlo_text, layer_elements):
    """Instructions outside fused computations that copy or slice (by
    opcode, or a fusion named after one) into a result with at least
    ``layer_elements`` elements: one layer's share of a pool."""
    found, fused = [], False
    for line in hlo_text.splitlines():
        if line.endswith("{"):  # a computation's header
            fused = line.lstrip("%").startswith("fused_computation")
            continue
        m = None if fused else _HLO_LINE.match(line)
        if not m:
            continue
        opcode, name = m["opcode"], m["name"]
        if not (opcode in _COPY_OPCODES or (opcode == "fusion" and any(
                w in name for w in ("copy", "slice", "transpose")))):
            continue
        largest = max(
            (math.prod(map(int, dims.split(",")))
             for dims in re.findall(r"\w+\[([\d,]+)\]", m["result"])),
            default=0)
        if largest >= layer_elements:
            found.append(f"{name} = {m['result']} {opcode}")
    return found


_SERVE_CELLS = {"gpt2-medium.serve-closed": (24, 1024, 16, 2048, 32),
                "gpt2-xl.serve-closed": (48, 1600, 25, 512, 8)}
_serve_cell_programs = {}


def _compile_serve_cell(device, cell, t):
    """A GPT serve cell's decode step (t = 1) or prefill call, at the
    cell's real depth, which compiles in seconds: two layers of the xl pool
    are small enough for the compiler to stage them whole in fast memory,
    which is another program than the one the chip runs. The attention
    kernels are forced and compiled (``"auto"`` would see the CPU here),
    as the chip has them. Compiled once for the tests that read it.
    Returns (cfg, compiled, one pool's shape)."""
    if (cell, t) not in _serve_cell_programs:
        n_layers, d_model, n_heads, blocks, batch = _SERVE_CELLS[cell]
        # a small vocabulary: the real table is larger than a layer of
        # xl's pool
        cfg = gpt.GPTConfig(vocab_size=2048, n_layers=n_layers,
                            d_model=d_model, n_heads=n_heads,
                            d_ff=4 * d_model, max_seq_len=1024,
                            attention_impl="flash")
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(paged_mod, "_should_interpret", lambda: False)
            _serve_cell_programs[cell, t] = (cfg, *_compile_paged_forward(
                cfg, device, blocks=blocks, block=16, batch=batch, t=t))
    return _serve_cell_programs[cell, t]


_each_serve_cell_program = pytest.mark.parametrize(
    "cell,t", [(cell, t) for cell in _SERVE_CELLS for t in (1, 128)],
    ids=[f"{cell}-{kind}" for cell in _SERVE_CELLS
         for kind in ("decode", "prefill")])


@_each_serve_cell_program
def test_paged_forward_copies_no_pool(v5e, cell, t):
    """At the two serve cells' geometries (16 heads x 64: a 1024-wide pool
    row; 25 x 64 = 1600, padded to 1664) the compiled program updates the
    donated pools in place: nothing as large as one layer's share of a
    pool is copied, sliced out or stacked back, and the program's
    temporaries stay under one pool (the weights come in the type they
    are multiplied in, so no copy of them is among the temporaries)."""
    cfg, compiled, pool_shape = _compile_serve_cell(v5e[0], cell, t)
    assert pool_shape[-1] % 128 == 0 and pool_shape[-1] >= cfg.d_model
    pool_elements = math.prod(pool_shape)
    assert _pool_sized_copies(compiled.as_text(),
                              pool_elements // cfg.n_layers) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * pool_elements


@pytest.mark.parametrize("cell", list(_SERVE_CELLS))
def test_decode_reads_the_pool_through_the_table_in_one_kernel(v5e, cell):
    """A decode step of either GPT serve cell holds the paged-attention
    kernel (one call, in the layer scan's body) and gathers nothing: no
    value shaped like a batch of whole tables (``[batch * 64, 16, R]`` as
    the gather makes it, ``[batch, 1024, ...]`` as attention reads it)
    exists in the program, and all its temporaries together are smaller
    than one such value. A prefill call keeps both gathers and holds no
    kernel."""
    cfg, compiled, pool_shape = _compile_serve_cell(v5e[0], cell, 1)
    batch, row = _SERVE_CELLS[cell][4], pool_shape[-1]
    tables = re.compile(rf"\w+\[(?:{batch * 64},16,{row}|{batch},1024,)")
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 1 and "paged_attn" in calls[0], calls
    assert not tables.search(text)
    assert compiled.memory_analysis().temp_size_in_bytes \
        < batch * 1024 * row * 2
    _, prefill, _ = _compile_serve_cell(v5e[0], cell, 128)
    assert "tpu_custom_call" not in prefill.as_text()
    assert len(set(tables.findall(prefill.as_text()))) == 2


@pytest.mark.parametrize("batch,heads", [(32, 16), (8, 25), (1, 16)],
                         ids=["medium", "xl", "one-row"])
def test_paged_kernel_compiles_at_the_serve_cells_shapes(v5e, batch, heads):
    """The kernel alone, at the rule's sizes and at the sweep's corners,
    within the fast memory it asks for (two slots of K and V at the
    table's whole length: 8 MiB at 1024 columns, 13 MiB at 1664)."""
    one = SingleDeviceSharding(v5e[0])
    row = -(-heads * 64 // 128) * 128

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    pool = arr((batch * 64 * 2, 16, row), jnp.bfloat16)
    for sz in (None, paged_mod.Sizes(1, 128, 8),
               paged_mod.Sizes(batch, 512, 2)):
        text = jax.jit(functools.partial(
            paged_mod.paged_attention, sz=sz, interpret=False)).lower(
                arr((batch, 1, heads, 64), jnp.bfloat16), pool, pool,
                arr((batch, 64), jnp.int32), arr((batch,), jnp.int32)
            ).compile().as_text()
        assert "tpu_custom_call" in text and "paged_attn" in text
    assert paged_mod._scratch_bytes(
        paged_mod.sizes(64, 16), paged_mod._padded_heads(
            heads, jnp.bfloat16), row, jnp.bfloat16) < 14 * 2 ** 20


@_each_serve_cell_program
def test_paged_forward_converts_and_copies_no_weight_stack(v5e, cell, t):
    """The block matrices arrive as ``[L, ...]`` stacks of the compute
    type, in the layout the chip holds such an array in. No instruction
    converts one or lays one out anew (xl's 1600 and 4800 are no
    multiples of 128: a layout copy of a stack in every program would be
    the casts under another name), and nothing of a stack's size is
    copied or sliced: a layer's matrix is read where it lies."""
    cfg, compiled, _ = _compile_serve_cell(v5e[0], cell, t)
    L, D = cfg.n_layers, cfg.d_model
    stack = re.compile(rf"\w+\[{L},(?:{D}|{3 * D}|{4 * D}),"
                       rf"(?:{D}|{3 * D}|{4 * D})\]")
    text = compiled.as_text()
    made = [m for m in map(_HLO_LINE.match, text.splitlines())
            if m and stack.search(m["result"])]
    # what makes a stack-shaped value: reading a parameter or the scan's
    # carry, never an operation on the data
    assert {m["opcode"] for m in made} <= {
        "parameter", "get-tuple-element", "bitcast"}, sorted(
            {(m["name"], m["opcode"]) for m in made})
    assert _pool_sized_copies(text, L * D * D) == []


_evabyte_programs = {}


def _compile_evabyte(device, batch, t):
    """A program of ``evabyte-6.5b.serve-doc-closed`` (16 layers at the
    published widths, a pool of 8 x (128 window + 64 summary) blocks), the
    decode kernel compiled as the chip has it (``fits`` would see the CPU
    here and hand every shape to the interpreter). Compiled once for the
    tests that read it. Returns (compiled, one pool's shape)."""
    from determined_clone_tpu.models import evabyte
    from determined_clone_tpu.serving.engine import make_paged_forward
    from determined_clone_tpu.serving.kv_cache import (
        KVCacheConfig,
        init_kv_pools,
    )

    if (batch, t) not in _evabyte_programs:
        cfg = evabyte.EvaByteConfig(n_layers=16, max_seq_len=16384)
        cache = KVCacheConfig(8 * 192, 16)
        layout = cfg.paged_model().cache_layout(cfg, cache)
        assert layout.blocks_needed(cfg.max_seq_len) == layout.table_width \
            == 192
        one = SingleDeviceSharding(device)
        params = _shapes(jax.eval_shape(lambda k: evabyte.init(k, cfg),
                                        jax.random.PRNGKey(0)), one)
        k_pool, v_pool = _shapes(jax.eval_shape(
            lambda: init_kv_pools(cfg, cache)), one)

        def arr(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(eva_paged_mod, "_should_interpret", lambda: False)
            _evabyte_programs[batch, t] = (make_paged_forward().lower(
                params, cfg, *_step_inputs(arr, batch, t, layout.table_width),
                k_pool, v_pool).compile(), k_pool.shape)
    return _evabyte_programs[batch, t]


@pytest.mark.parametrize("batch,t", [(8, 1), (1, 2048), (8, 2048)],
                         ids=["decode", "slice", "slices-of-8-rows"])
def test_evabyte_paged_forward_compiles_at_the_cell_sizes(v5e, batch, t):
    """``evabyte-6.5b.serve-doc-closed``: 16 layers at the published widths
    (6.5 GB of bfloat16 weights), a pool of 8 x (128 window + 64 summary)
    blocks (6.4 GB), the decode step and the 2048-token prefill slice. They
    fit the 15.75 GB a v5e offers a program, the donated pools are updated
    in place, and no program casts a weight: a matrix is read as it lies."""
    compiled, pool_shape = _compile_evabyte(v5e[0], batch, t)
    mem = compiled.memory_analysis()
    pool_bytes = 2 * math.prod(pool_shape)
    assert mem.alias_size_in_bytes >= 2 * pool_bytes  # both pools, in place
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 15.75 * 2 ** 30)
    text = compiled.as_text()
    # of the pool, that is: the 8-row prefill also lays one [16, D, D]
    # weight out anew, once a call (a third of a millisecond)
    L, N, bs, R = pool_shape
    pool_dims = (f"{N},{bs},{R}]", f"[{L * N * bs},{R}]", f"[{N * bs},{R}]")
    assert [c for c in _pool_sized_copies(text, N * bs * R)
            if any(d in c for d in pool_dims)] == []
    weight = re.compile(r"= \w+\[(?:16,)?(?:4096|11008),(?:4096|11008)\]"
                        r"[^ ]* convert\(")
    assert [ln for ln in text.splitlines() if weight.search(ln)] == []


def test_evabyte_decode_reads_the_pool_through_the_table_in_one_kernel(v5e):
    """The cell's decode step holds the EVA paged-attention kernel (one
    call, in the layer scan's body, under ``attn/eva_attn``: the scope the
    benchmark's ``decode_eva_attn_device_ms`` reads) and neither multiplies
    a query with a layer's whole share of the pool (scores ``[8, 32,
    24576]``, a context ``[24576, 4096]``) nor gathers a batch of whole
    tables (``[8, 3072, 4096]``); all its temporaries together are smaller
    than one table's K rows. A slice keeps the gather and holds no
    kernel."""
    compiled, pool_shape = _compile_evabyte(v5e[0], 8, 1)
    L, N, bs, R = pool_shape
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 1 and "eva_paged_attn" in calls[0], calls
    assert "attn/eva_attn" in calls[0]
    whole = re.compile(rf"\w+\[(?:8,32,{N * bs}|{N * bs},{R}|8,{N * bs // 8},"
                       rf"{R}|{N},{bs},{R})\]")
    assert not whole.search(text), whole.search(text).group(0)
    assert compiled.memory_analysis().temp_size_in_bytes \
        < N * bs // 8 * R * 2
    slice_text = _compile_evabyte(v5e[0], 1, 2048)[0].as_text()
    assert "tpu_custom_call" not in slice_text


@pytest.mark.parametrize("batch", [1, 2, 4, 8])
def test_evabyte_paged_kernel_compiles_at_the_decode_ladder(v5e, batch):
    """The kernel alone at every batch bucket of the cell's decode ladder,
    within the fast memory it asks for (two buffers each of K and V, 2 MB a
    piece, and a whole table's scores)."""
    one = SingleDeviceSharding(v5e[0])

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    pool = arr((16 * 8 * 192, 16, 4096), jnp.bfloat16)
    rows = arr((batch,), jnp.int32)
    text = jax.jit(functools.partial(
        eva_paged_mod.eva_paged_attention, window_blocks=128,
        interpret=False)).lower(
            arr((batch, 1, 32, 128), jnp.bfloat16), pool, pool,
            arr((batch, 192), jnp.int32), rows, rows).compile().as_text()
    assert "tpu_custom_call" in text and "eva_paged_attn" in text
    assert eva_paged_mod._scratch_bytes(
        eva_paged_mod.sizes(192, 16), 32, 4096, jnp.bfloat16) < 10 * 2 ** 20


@pytest.mark.parametrize("batch,t", [(8, 1), (1, 2048), (8, 2048)],
                         ids=["decode", "slice", "slices-of-8-rows"])
def test_minicpm_sala_paged_forward_compiles_at_the_cell_sizes(v5e, batch,
                                                               t):
    """``minicpm-sala-9b.serve-long-closed``: published layers 9..16 at the
    published widths (5.6 GB of bfloat16 weights), a pool of 8 x 544 blocks
    of 64 positions for the two sparse layers with their compressed keys,
    and 8 x 6 recurrent states; the decode step at 8 rows and the
    2048-token prefill slice at 34816 positions. They fit the 15.75 GB a
    v5e offers a program, all four donated pools are updated in place, and
    no program casts or copies a stack of weights."""
    from determined_clone_tpu.models import minicpm_sala
    from determined_clone_tpu.serving.engine import make_paged_forward
    from determined_clone_tpu.serving.kv_cache import KVCacheConfig

    cfg = minicpm_sala.MiniCPMSALAConfig(
        mixer_types=minicpm_sala._PUBLISHED_MIXERS[9:17], first_layer=9,
        max_seq_len=34816)
    assert cfg.runs() == [("minicpm4", 0, 1), ("lightning-attn", 0, 6),
                          ("minicpm4", 1, 2)]
    cache = KVCacheConfig(8 * 544, 64)
    layout = cfg.paged_model().cache_layout(cfg, cache)
    assert layout.blocks_needed(cfg.max_seq_len) == 544
    assert layout.table_width == 545
    one = SingleDeviceSharding(v5e[0])
    params = _shapes(jax.eval_shape(
        lambda k: minicpm_sala.serving_params(minicpm_sala.init(k, cfg),
                                              cfg),
        jax.random.PRNGKey(0)), one)
    pools = _shapes(jax.eval_shape(
        lambda: minicpm_sala.init_pools(cfg, cache, 8)), one)
    assert [p.shape for p in pools] == [
        (2, 4352, 64, 256), (2, 4352, 64, 256), (2, 4352, 1024),
        (6, 8, 32, 128, 128)]

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    compiled = make_paged_forward(len(pools)).lower(
        params, cfg, *_step_inputs(arr, batch, t, layout.table_width),
        *pools).compile()
    mem = compiled.memory_analysis()
    pool_bytes = sum(math.prod(p.shape) * p.dtype.itemsize for p in pools)
    assert mem.alias_size_in_bytes >= pool_bytes      # every pool, in place
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 15.75 * 2 ** 30)
    # no weight is cast (a convert of a fused operand is read as it lies),
    # and no stack of weights is copied but into VMEM (memory space S(1));
    # the 8-row prefill lays the lightning q, k, v stacks out anew, once a
    # call of eight slices (1.5 ms at the memory's peak)
    weight = r"= \w+\[(?:[26],)?(?:4096|16384),(?:4096|16384|256)\][^ ]* "
    lines = compiled.as_text().splitlines()
    assert [ln for ln in lines if re.search(weight + r"convert\((?!%param_)",
                                            ln)] == []
    copies = [ln for ln in lines if re.search(weight + r"copy\(", ln)
              and "S(1)}" not in ln]
    assert copies == [] or (batch, t) == (8, 2048), copies[:3]


def _mla_slice_calls(hlo_text: str) -> int:
    """Mosaic calls of the kernel ``mla_slice`` under the scope
    ``mla_attn``."""
    return sum("tpu_custom_call" in line and bool(re.search(
        r"/mla_attn/(?:jit\(_slice_call\)/)?mla_slice/", line))
        for line in hlo_text.splitlines())


@pytest.mark.parametrize("heads,table", [(64, 512), (32, 800)],
                         ids=["glm-5.2", "kimi-linear"])
@pytest.mark.parametrize("t", [512, 1024, 2048])
def test_mla_slice_kernel_compiles_at_the_serve_cells_shapes(
        v5e, monkeypatch, t, heads, table):
    """The prefill buckets of both latent-attention serve cells, alone:
    rows of 640 in blocks of 64, 64 heads under a mask of chosen positions
    over a table of 512 blocks, 32 heads under the causal mask over 800."""
    from determined_clone_tpu.ops import mla_attention as mla_mod

    monkeypatch.setattr(mla_mod, "_should_interpret", lambda: False)
    one = SingleDeviceSharding(v5e[0])

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    chosen = heads == 64
    args = [arr((1, t, heads, 640), jnp.bfloat16),
            arr((4 * table, 64, 640), jnp.bfloat16),
            arr((1, table), jnp.int32), arr((1, t), jnp.int32),
            arr((1, t), jnp.bool_)]
    if chosen:
        args.append(arr((1, t, table * 64), jnp.bool_))

    def call(q, blocks, tables, positions, mask, allowed=None):
        return mla_mod.mla_slice(q, blocks, tables, allowed, positions, mask,
                                 scale=0.07, rank=512)

    compiled = jax.jit(call).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 1
    # the latents are read where they lie: no row's table of them is made
    assert not re.search(rf"bf16\[(?:1,)?{table * 64},640\]",
                         compiled.as_text())


@pytest.mark.parametrize("batch,t", [(1, 1), (16, 1), (1, 512), (1, 2048),
                                     (16, 2048)],
                         ids=["decode-1", "decode-16", "slice-512",
                              "slice-2048", "slices-of-16-rows"])
def test_glm_moe_dsa_paged_forward_compiles_at_the_cell_sizes(
        v5e, monkeypatch, batch, t):
    """``glm-5.2.serve-agent-closed``: published layers 2..6 at the
    published widths with 16 of the 256 experts held (7.8 GB of bfloat16
    weights), 16 x 512 blocks of 64 positions of latents in five layers
    (rows of 576 padded to 640) and of indexer keys in the two ``full``
    ones (3.6 GB); the decode step at 1 and 16 rows, and the prefill
    slices at 32768 positions. They fit the 15.75 GB a v5e offers a
    program, both donated pools are updated in place, nothing as large as
    a layer's share of a pool or a row's whole table of latents is copied
    or gathered, no stack of weights is converted, a slice's latent
    attention is one Mosaic call a layer under ``mla_attn`` (no pass's
    ``[512, 64, 2048]`` scores in the program), and the five scopes the
    benchmark reads are on the operations' paths."""
    from determined_clone_tpu.models import glm_moe_dsa as glm
    from determined_clone_tpu.ops import mla_attention as mla_mod
    from determined_clone_tpu.serving.engine import make_paged_forward
    from determined_clone_tpu.serving.kv_cache import KVCacheConfig

    monkeypatch.setattr(mla_mod, "_should_interpret", lambda: False)
    cfg = glm.GLMMoeDsaConfig(
        vocab_size=19360, mlp_layer_types=glm._PUBLISHED_MLPS[2:7],
        indexer_types=glm._PUBLISHED_INDEXERS[2:7], n_routed_experts=16,
        max_position_embeddings=32768)
    assert cfg.kinds == ("dense_full", "sparse_shared", "sparse_shared",
                         "sparse_shared", "sparse_full")
    cache = KVCacheConfig(16 * 512, 64)
    layout = cfg.paged_model().cache_layout(cfg, cache)
    assert layout.blocks_needed(cfg.max_seq_len) == layout.table_width == 512
    one = SingleDeviceSharding(v5e[0])
    params = _shapes(jax.eval_shape(
        lambda k: glm.serving_params(glm.init(k, cfg), cfg),
        jax.random.PRNGKey(0)), one)
    assert 7.7e9 < sum(math.prod(x.shape) * x.dtype.itemsize
                       for x in jax.tree.leaves(params)) < 7.8e9
    pools = _shapes(jax.eval_shape(lambda: glm.init_pools(cfg, cache, 16)),
                    one)
    assert [p.shape for p in pools] == [(5, 8192, 64, 640),
                                        (2, 8192, 64, 128)]

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    compiled = make_paged_forward(len(pools)).lower(
        params, cfg, *_step_inputs(arr, batch, t, layout.table_width),
        *pools).compile()
    mem = compiled.memory_analysis()
    pool_bytes = sum(math.prod(p.shape) * p.dtype.itemsize for p in pools)
    assert mem.alias_size_in_bytes >= pool_bytes      # both pools, in place
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 15.75 * 2 ** 30)
    text = compiled.as_text()
    # nothing shaped like a layer of a pool, and no sequence's whole table
    # of latents ([32768, 640] a row): a decode step gathers its 2048
    # chosen rows, a slice reads a chunk of blocks a pass
    N, bs, R = pools[0].shape[1:]
    pool_like = [c for c in _pool_sized_copies(text, 32768 * 128)
                 if re.search(rf"\[(?:\d+,)?(?:{N},{bs}|{N * bs}|32768),"
                              rf"(?:{R}|128)\]", c)]
    assert pool_like == [], pool_like[:3]
    if t == 1:
        assert mem.temp_size_in_bytes < 0.25 * 2 ** 30
        assert not re.search(rf"bf16\[{batch},32768,{R}\]", text)
        assert re.search(rf"bf16\[(?:{batch},)?2048,{R}\]", text)  # chosen
    else:
        # one a layer: a run of layers of one kind is one loop's body
        assert _mla_slice_calls(text) == len(cfg.runs()) == 3
        assert not re.search(r"f32\[(?:\d+,)*512,64,2048\]", text)
    # no matrix is converted, as a stack or as a layer of one: it is read
    # as it lies (the head alone is raised, for the one row of logits a
    # slice returns: 0.24 GB read once a call). Shapes that a slice's
    # activations share (those with its length among their sizes: 512 is
    # the latent's rank too) are left to the other cases
    shapes = set()
    for kind in set(cfg.kinds):
        n = cfg.kinds.count(kind)
        for path, shape in glm.layer_shapes(cfg, kind).items():
            if path.endswith("/kernel") and t not in shape:
                shapes |= {shape, (n, *shape), (n * shape[0], *shape[1:])}
    converted, fused = [], False
    for line in text.splitlines():   # a fused convert is read as it lies
        if line.endswith("{"):
            fused = line.lstrip("%").startswith("fused_computation")
        m = None if fused else _HLO_LINE.match(line)
        if m and "convert" in (m["opcode"], *m["name"].split("_")) and any(
                tuple(map(int, dims.split(","))) in shapes
                for dims in re.findall(r"\w+\[([\d,]+)\]", m["result"])):
            converted.append(m["name"])
    assert converted == [], converted[:5]
    for scope in ("dsa_index", "mla_attn", "kv_cache", "moe_route",
                  "moe_experts"):
        assert f"/{scope}/" in text, scope


@pytest.mark.parametrize("batch,t", [(32, 1), (1, 2048), (32, 2048)],
                         ids=["decode-32", "slice-2048",
                              "slices-of-32-rows"])
def test_kimi_linear_paged_forward_compiles_at_the_cell_sizes(
        v5e, monkeypatch, batch, t):
    """``kimi-linear-48b-a3b.serve-longdoc-closed``: published layers 1..5
    at the published widths with 128 of the 256 experts held and half the
    vocabulary (8.57 GB of bfloat16 weights), 32 x 800 blocks of 64
    positions of latents in the one MLA layer (2.1 GB), 32 slots of four
    states and tails (0.28 GB); the decode step at 32 rows and the prefill
    slices at 51200 positions. They fit the 15.75 GB a v5e offers a
    program, all three donated pools are updated in place, the decode step
    makes nothing as large as one row's table of latents, a slice runs the
    chunked delta rule as a Mosaic call under ``kda`` (no chunk's ``[64,
    64, 128]`` decays and no loop of 64 rows in the program) and the MLA
    layer's attention as one under ``mla_attn`` (no pass's ``[512, 32,
    2048]`` scores), and the six scopes the benchmark reads are on the
    operations' paths."""
    from determined_clone_tpu.models import kimi_linear as kl
    from determined_clone_tpu.ops import kda as kda_mod
    from determined_clone_tpu.ops import mla_attention as mla_mod
    from determined_clone_tpu.serving.engine import make_paged_forward
    from determined_clone_tpu.serving.kv_cache import KVCacheConfig

    monkeypatch.setattr(kda_mod, "_should_interpret", lambda: False)
    monkeypatch.setattr(mla_mod, "_should_interpret", lambda: False)
    cfg = kl.KimiLinearConfig(
        vocab_size=81920, num_hidden_layers=5, kda_layers=(1, 2, 3, 5),
        full_attn_layers=(4,), num_experts=128, model_max_length=51200)
    cache = KVCacheConfig(32 * 800, 64)
    layout = cfg.paged_model().cache_layout(cfg, cache)
    assert layout.blocks_needed(cfg.max_seq_len) == 800 \
        == layout.table_width - 1
    one = SingleDeviceSharding(v5e[0])
    params = _shapes(jax.eval_shape(
        lambda k: kl.serving_params(kl.init(k, cfg), cfg),
        jax.random.PRNGKey(0)), one)
    assert 8.5e9 < sum(math.prod(x.shape) * x.dtype.itemsize
                       for x in jax.tree.leaves(params)) < 8.6e9
    pools = _shapes(jax.eval_shape(lambda: kl.init_pools(cfg, cache, 32)),
                    one)
    assert [p.shape for p in pools] == [(1, 25600, 64, 640),
                                        (4, 32, 32, 128, 128),
                                        (4, 32, 3, 12288)]

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    compiled = make_paged_forward(len(pools)).lower(
        params, cfg, *_step_inputs(arr, batch, t, layout.table_width),
        *pools).compile()
    mem = compiled.memory_analysis()
    pool_bytes = sum(math.prod(p.shape) * p.dtype.itemsize for p in pools)
    assert mem.alias_size_in_bytes >= pool_bytes      # all three, in place
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 15.75 * 2 ** 30)
    text = compiled.as_text()
    if t == 1:
        # a pass of 32 blocks a row, not a row's table (51200 x 640)
        assert mem.temp_size_in_bytes < 0.25 * 2 ** 30
        assert not re.search(r"bf16\[(?:\d+,)?51200,640\]", text)
    else:
        # the chunked delta rule is the kernel: no [64, 64, 128] decays of
        # a chunk anywhere in the program, no loop (of a chunk's 64 rows, or
        # over chunks) left to XLA under the scope
        assert mem.temp_size_in_bytes < 1.0 * 2 ** 30
        kda_lines = [line for line in text.splitlines() if "/kda/" in line]
        assert any("tpu_custom_call" in line for line in kda_lines)
        assert not re.search(r"f32\[(?:\d+,)*64,64,128\]", text)
        assert not any("/kda/while" in line for line in kda_lines)
        assert _mla_slice_calls(text) == len(cfg.full_attn_layers)
        assert not re.search(r"f32\[(?:\d+,)*512,32,2048\]", text)
    for scope in ("kda", "kda_conv", "mla_attn", "kv_cache", "moe_route",
                  "moe_experts"):
        assert f"/{scope}/" in text, scope


@pytest.mark.parametrize("batch,t", [(16, 1), (1, 2048), (16, 2048)],
                         ids=["decode-16", "slice-2048",
                              "slices-of-16-rows"])
def test_afmoe_paged_forward_compiles_at_the_cell_sizes(v5e, batch, t):
    """``trinity-large-preview.serve-mixed-closed``: published layers 6..10
    at the published widths with 32 of the 256 experts held and an eighth
    of the vocabulary (8.65 GB of bfloat16 weights), 16 x 544 blocks of 64
    positions of K and V rows of 1024 in the one full layer (2.28 GB), 16
    slots of four rings of 6144 positions (1.61 GB); the decode step at 16
    rows and the prefill slices at 34816 positions. They fit the 15.75 GB
    a v5e offers a program, all four donated pools are updated in place,
    neither form makes anything as large as one row's table of K or V rows
    (a pass of blocks a row, under an online softmax) or a slice's scores
    over a whole context, and the six scopes the benchmark reads are on
    the operations' paths."""
    from determined_clone_tpu.models import afmoe
    from determined_clone_tpu.serving.engine import make_paged_forward
    from determined_clone_tpu.serving.kv_cache import KVCacheConfig

    cfg = afmoe.AfmoeConfig(
        vocab_size=25024, num_hidden_layers=5, num_dense_layers=1,
        layer_types=("sliding_attention",) * 2 + ("full_attention",)
        + ("sliding_attention",) * 2, num_experts=32,
        max_position_embeddings=34816)
    cache = KVCacheConfig(16 * 544, 64)
    layout = cfg.paged_model().cache_layout(cfg, cache)
    assert layout.blocks_needed(cfg.max_seq_len) == 544 \
        == layout.table_width - 1
    assert (layout.window, layout.ring) == (4096, 6144)
    one = SingleDeviceSharding(v5e[0])
    params = _shapes(jax.eval_shape(
        lambda k: afmoe.serving_params(afmoe.init(k, cfg), cfg),
        jax.random.PRNGKey(0)), one)
    assert 8.6e9 < sum(math.prod(x.shape) * x.dtype.itemsize
                       for x in jax.tree.leaves(params)) < 8.7e9
    pools = _shapes(jax.eval_shape(lambda: afmoe.init_pools(cfg, cache, 16)),
                    one)
    assert [p.shape for p in pools] == [(1, 8704, 64, 1024)] * 2 \
        + [(4, 16, 6144, 1024)] * 2
    pool_bytes = sum(math.prod(p.shape) * p.dtype.itemsize for p in pools)
    assert 3.89e9 < pool_bytes < 3.90e9

    def arr(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    compiled = make_paged_forward(len(pools)).lower(
        params, cfg, *_step_inputs(arr, batch, t, layout.table_width),
        *pools).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pool_bytes      # all four, in place
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 15.75 * 2 ** 30)
    text = compiled.as_text()
    # no row's ring or table of rows, no scores over a ring or a context
    assert not re.search(r"bf16\[(?:\d+,)?(?:6144|34816),1024\]", text)
    assert not re.search(r"f32\[(?:\d+,)*34816\]", text)
    if t == 1:
        assert mem.temp_size_in_bytes < 0.25 * 2 ** 30
    else:
        assert mem.temp_size_in_bytes < 2.0 * 2 ** 30
    for scope in ("window_attn", "full_attn", "kv_cache", "moe_route",
                  "moe_experts"):
        assert f"/{scope}/" in text, scope


@pytest.mark.slow
@pytest.mark.parametrize("n_chips", [1, 4])
def test_gpt2_small_train_step_compiles(v5e, monkeypatch, n_chips):
    """The whole fsdp.yaml train step (batch 8 x 1025 tokens, AdamW + clip,
    flash attention), built the way the trainer builds it: the kernel is in
    the program, the program fits the chip, and on four chips the FSDP
    collectives are there.

    Marked slow (12 s a case): the tier-1 lane already runs into its 870 s
    limit, so it keeps the kernels and the paged step above and leaves this
    to ``pytest tests/test_chip_compile.py`` before a chip call."""
    from determined_clone_tpu.telemetry import collectives

    monkeypatch.setattr(flash_mod, "_should_interpret", lambda: False)
    config = chip_smoke.experiment_config(
        n_chips, widths={"attention_impl": "flash"}, max_batches=1)
    hparams = config.hyperparameters.sample(random.Random(0))
    mesh = make_mesh(MeshSpec(fsdp=n_chips), v5e[:n_chips])
    compiled = chip_smoke.compile_train_step(config, hparams, mesh)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    mem = compiled.memory_analysis()  # per device
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < V5E_HBM_BYTES)
    coll = collectives.parse_hlo_collectives(text, mesh=mesh)
    if n_chips == 1:
        assert coll.total_ops == 0
    else:
        assert coll.count("all-gather", "fsdp") > 0
        assert (coll.count("reduce-scatter", "fsdp")
                + coll.count("all-reduce", "fsdp")) > 0


# ---------------------------------------------------------------------------
# chip_smoke.py on the CPU: the phase functions at GPTConfig.tiny() widths
# ---------------------------------------------------------------------------

_TINY = gpt.GPTConfig.tiny()
TINY_WIDTHS = dict(vocab_size=_TINY.vocab_size, n_layers=_TINY.n_layers,
                   d_model=_TINY.d_model, n_heads=_TINY.n_heads,
                   d_ff=_TINY.d_ff, seq_len=_TINY.max_seq_len // 2,
                   n_train_tokens=20_000)


@pytest.mark.parametrize("phase", [
    "train", "serve", pytest.param("sharded", marks=pytest.mark.slow)])
def test_smoke_phase_rehearsal_on_cpu(tmp_path, phase):
    if phase == "train":
        out = chip_smoke.train_phase(str(tmp_path), widths=TINY_WIDTHS,
                                     units=2)
        assert out["reports_at"] == [20, 40, 41]
        assert out["checkpoint_restored"] and out["last_loss"] < out[
            "first_loss"]
        assert out["mfu_peak_label"] == "cpu:est"
        # off the chip "auto" resolves to plain XLA attention
        assert out["attention_impl"] == "mha"
        assert not out["pallas_custom_call_in_step"]
    elif phase == "serve":
        # the phase counts the programs its own engine added, as the
        # serving tests do: no jit cache is cleared for it or after it
        out = chip_smoke.serve_phase(_TINY)
        assert out["requests"] == len(chip_smoke.SERVE_REQUESTS)
        assert out["tokens_match_reference"]  # bit-identical on the CPU
        assert out["leaked_kv_blocks"] == 0 and out["peak_active"] >= 2
    else:
        if len(jax.devices()) < 4:
            pytest.skip("needs four (virtual) devices")
        out = chip_smoke.sharded_phase(str(tmp_path), n_chips=4,
                                       widths=TINY_WIDTHS, steps=4)
        assert out["sharded"]["mesh"] == {"fsdp": 4}
        assert out["sharded"]["leaves_misplaced"] == 0
        assert out["max_relative_loss_gap"] < 1e-3


def test_smoke_refuses_to_run_without_a_tpu():
    """No accelerator: non-zero exit and no result line."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "no TPU" in proc.stderr


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_is_placed_from_outside(monkeypatch, tmp_path,
                                              from_env):
    """JAX_COMPILATION_CACHE_DIR stands where it is set (and no other
    directory is set in code); unset, the cache is <repo>/.jax_cache."""
    was = jax.config.jax_compilation_cache_dir
    was_min = jax.config.jax_persistent_cache_min_compile_time_secs
    monkeypatch.delenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                       raising=False)
    try:
        if from_env:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert compile_cache.configure_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == was
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            path = compile_cache.configure_compile_cache()
            assert path == os.path.join(REPO, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
        # sub-second programs are kept too (the warm-up ladder is made of
        # them), unless the threshold was set from outside
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          was_min)


# What a process compiles, run as a child so that its jit caches start
# empty: the last line says what JAX's persistent cache was asked and gave.
_CACHE_CHILD = """
import collections, json, sys
import jax
from jax import monitoring
from determined_clone_tpu.utils.compile_cache import configure_compile_cache

events = collections.Counter()
monitoring.register_event_listener(lambda name, **kw: events.update([name]))
configure_compile_cache()
from determined_clone_tpu.models import gpt
cfg = gpt.GPTConfig.tiny()
params = gpt.init(jax.random.PRNGKey(0), cfg)
if sys.argv[1] == "serving_ladder":
    from determined_clone_tpu.serving import (
        BucketSpec, InferenceEngine, KVCacheConfig)
    with InferenceEngine(params, cfg, buckets=BucketSpec.build(2, 8),
                         cache=KVCacheConfig(8, 8)) as eng:
        programs = eng.warmup()
        assert programs == eng.program_budget()
else:
    import optax
    from determined_clone_tpu.training.train_step import (
        capture_compile, create_train_state, make_train_step)
    tx = optax.adamw(1e-3)
    state = create_train_state(params, tx, jax.random.PRNGKey(1))
    tokens = jax.numpy.zeros((2, 17), jax.numpy.int32)
    step = make_train_step(
        lambda p, b, rng: gpt.loss_fn(p, cfg, b[:, :-1], b[:, 1:]), tx)
    step, record = capture_compile(step, (state, tokens))
    assert record is not None
    jax.block_until_ready(step(state, tokens))
    programs = 1
prefix = "/jax/compilation_cache/"
print(json.dumps({"programs": programs, **{
    k: events[prefix + k] for k in (
        "compile_requests_use_cache", "cache_hits", "cache_misses")}}))
"""


@pytest.mark.parametrize("what", ["serving_ladder", "trainer_step"])
def test_second_process_compiles_from_the_persistent_cache(tmp_path, what):
    """Two processes with one JAX_COMPILATION_CACHE_DIR: the first
    compiles the engine's whole bucket ladder (or the trainer's captured
    step) and writes it; the second is given every program by the cache,
    by JAX's own count of hits and misses."""
    import json

    def child():
        proc = subprocess.run(
            [sys.executable, "-c", _CACHE_CHILD, what], cwd=REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO,
                 "JAX_COMPILATION_CACHE_DIR": str(tmp_path)},
            capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr[-2000:]
        return json.loads(proc.stdout.splitlines()[-1])

    cold, warm = child(), child()
    assert cold["cache_hits"] == 0
    assert cold["cache_misses"] >= cold["programs"] > 0
    assert warm["cache_misses"] == 0
    assert (warm["cache_hits"] == warm["compile_requests_use_cache"]
            == cold["compile_requests_use_cache"])
