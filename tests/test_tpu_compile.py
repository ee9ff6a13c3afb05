"""Compile the serving path's Pallas kernels for a described TPU v5e chip.

Interpret mode runs a kernel's arithmetic on the CPU but not the chip's
compiler, which refuses blocks off the (8, 128) tiling and kernels that
overrun fast memory.  TPU's compiler is installed with jax, and it compiles
for a chip that is only described, so these cases lower and compile every
kernel of the main path at gemma3-1b widths (decode M=8, chunk M=64, the
8x1152x262144 tied unembedding) for one chip of a v5e:2x2 topology.  The
flash-decode cases take the smallest and largest split factor the decode
tuner can bind (1 and 16; the split axis is outside every block's last two
dims, so those between lay out alike), MQA and the GQA layout (8 kv heads,
D=128), float and int8 KV pools, in the decode and prefill-chunk steps; at
mistral-nemo-12b's decode and 256-token chunk shapes they also check that
the chip benchmark still tells the flash-decode kernel by its operands.
Two cases compile mistral-nemo-12b's whole donated decode and chunk steps
at depth 8 and check that no instruction makes a whole KV pool.

Nothing runs: a pass here is a compile, not a chip run.  Only one process
may load the TPU library at a time, so the topology is described inside a
module fixture (never at import) and every case compiles in this process.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import flash_decode as fd
from repro.kernels import ops
from repro.kernels.quant import quantize_rows
from repro.serving import kv_cache as kvc

D_MODEL, D_FF, VOCAB, Q_WIDTH = 1152, 6912, 262144, 4 * 256   # gemma3-1b
GEMM_SHAPES = [
    (8, D_MODEL, D_FF),          # decode FFN up
    (64, D_FF, D_MODEL),         # chunk FFN down
    (64, D_MODEL, Q_WIDTH),      # chunk q projection
    (8, D_MODEL, VOCAB),         # decode tied unembedding
]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # A compile for a described chip is written to the persistent cache
        # but cannot be read back without one: keep the cache out of it.
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


def _compile(one_chip, fn, *shapes):
    """Lower ``fn`` at ``shapes`` (dtype, shape) for the described chip,
    compile, and check a Pallas kernel is in the program."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for d, s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("m,k,n", GEMM_SHAPES)
def test_gemm_compiles(one_chip, m, k, n):
    _compile(one_chip, lambda a, b: ops.gemm(a, b, backend="pallas"),
             (jnp.bfloat16, (m, k)), (jnp.bfloat16, (k, n)))


@pytest.mark.parametrize("m,k,n", GEMM_SHAPES)
def test_dequant_gemm_compiles(one_chip, m, k, n):
    _compile(one_chip,
             lambda a, b, sa, sb: ops.gemm_int8_dequant(
                 a, b, sa, sb, backend="pallas"),
             (jnp.int8, (m, k)), (jnp.int8, (k, n)),
             (jnp.float32, (m, 1)), (jnp.float32, (1, n)))


@pytest.mark.parametrize("m,k,n", GEMM_SHAPES)
def test_w8a8_gemm_compiles(one_chip, m, k, n):
    _compile(one_chip,
             lambda x, w, s: ops.gemm_w8a8(x, w, s, backend="pallas"),
             (jnp.bfloat16, (m, k)), (jnp.int8, (k, n)),
             (jnp.float32, (1, n)))


@pytest.mark.parametrize("m,k", [(8, D_MODEL), (64, D_FF)])
def test_quantize_rows_compiles(one_chip, m, k):
    _compile(one_chip, lambda x: quantize_rows(x, block_m=m),
             (jnp.bfloat16, (m, k)))


def test_gemm_pipelined_compiles(one_chip):
    _compile(one_chip, lambda a, b: ops.gemm(a, b, backend="pipelined"),
             (jnp.bfloat16, (64, D_MODEL)), (jnp.bfloat16, (D_MODEL, D_FF)))


def test_flash_attention_window_compiles(one_chip):
    """gemma3-1b local attention: MQA, D=256, sliding window 512."""
    _compile(one_chip,
             lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                                window=512),
             (jnp.bfloat16, (1, 1024, 4, 256)),
             (jnp.bfloat16, (1, 1024, 1, 256)),
             (jnp.bfloat16, (1, 1024, 1, 256)))


@pytest.mark.parametrize("kv_precision", ["float", "int8"])
@pytest.mark.parametrize("hkv,groups,d", [(1, 4, 256), (8, 5, 128)],
                         ids=["mqa-d256", "gqa8-d128"])
@pytest.mark.parametrize("splits", [1, 16])
@pytest.mark.parametrize("slots,sq", [(8, 1), (1, 64)],
                         ids=["decode", "chunk64"])
def test_flash_decode_compiles(one_chip, slots, sq, splits, hkv, groups, d,
                               kv_precision):
    """64 table columns of 16-token blocks (max_seq 1024) per slot, walked
    in eight 128-key steps, unsplit or split as far as they go, in the
    decode step (8 slots, one query each) and the prefill-chunk step (one
    slot, 64 queries)."""
    bs, max_blocks = 16, 64
    nb = 1 + 8 * max_blocks
    pool = jax.eval_shape(lambda: kvc.init_paged_kv(
        nb, bs, hkv, d, jnp.bfloat16, kv_precision=kv_precision))
    spec = fd.FlashDecodeSpec(num_splits=splits)

    def step(q, bt, idx, *leaves):
        cache = jax.tree_util.tree_unflatten(treedef, leaves)
        return fd.flash_decode_attention(q, cache, bt, idx, window=512,
                                         spec=spec)

    leaves, treedef = jax.tree_util.tree_flatten(pool)
    _compile(one_chip, step,
             (jnp.bfloat16, (slots, sq, hkv * groups, d)),
             (jnp.int32, (slots, max_blocks)), (jnp.int32, (slots,)),
             *[(x.dtype, x.shape) for x in leaves])


def _kernel_of():
    """benchmarks/chip/benchlib/trace.kernel_of, loaded by path: how the
    chip benchmark tells the flash-decode kernel in a profile."""
    import importlib.util
    import os
    import sys

    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "chip", "benchlib", "trace.py")
    spec = importlib.util.spec_from_file_location("_bench_trace", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod        # its dataclasses look it up there
    spec.loader.exec_module(mod)
    return mod.kernel_of


@pytest.mark.parametrize("slots,sq", [(32, 1), (1, 256)],
                         ids=["decode", "chunk256"])
def test_flash_decode_compiles_at_nemo_shapes(one_chip, slots, sq):
    """mistral-nemo-12b's serving shapes: 8 kv heads of 4 query heads at
    D=128, 16-token blocks, 256 table columns (max_seq 4096); the decode
    step (32 slots, one query each) and a 256-token prefill chunk.  The
    compiled kernel still reads as "flash_decode" to the chip benchmark,
    whose roofline metric would otherwise fall silent."""
    hkv, groups, d, bs, max_blocks = 8, 4, 128, 16, 256
    nb = 1 + 32 * max_blocks
    pool = jax.eval_shape(lambda: kvc.init_paged_kv(
        nb, bs, hkv, d, jnp.bfloat16))
    leaves, treedef = jax.tree_util.tree_flatten(pool)

    def step(q, bt, idx, *leaves):
        cache = jax.tree_util.tree_unflatten(treedef, leaves)
        return fd.flash_decode_attention(q, cache, bt, idx)

    compiled = _compile(one_chip, step,
                        (jnp.bfloat16, (slots, sq, hkv * groups, d)),
                        (jnp.int32, (slots, max_blocks)),
                        (jnp.int32, (slots,)),
                        *[(x.dtype, x.shape) for x in leaves])
    assert _kernels(compiled) == ["flash_decode"]


def _kernels(compiled):
    """What the chip benchmark reads each Pallas call of a compiled program
    as: the profile names an operation by its HLO text with operand
    shapes."""
    from jax._src.lib import xla_client

    opts = xla_client._xla.HloPrintOptions.short_parsable()
    opts.print_operand_shape = True
    [module] = compiled.runtime_executable().hlo_modules()
    kernel_of = _kernel_of()
    return [kernel_of(line) for line in module.to_string(opts).splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


def _whole_pool_ops(hlo: str, pool_shapes):
    """Instructions of a compiled program that make a whole pool: those
    outside fused computations whose output holds one of ``pool_shapes``,
    other than a parameter, a tuple or its element, a bitcast, a while
    loop's carry, or a fusion whose only pool-shaped work is
    dynamic-update-slice (written in place into its operand's buffer).  A
    copy, an allocation, a relayout or a scatter of a pool is listed."""
    shapes = tuple(f"[{','.join(map(str, s))}]" for s in pool_shapes)
    comps, name = {}, None
    for line in hlo.splitlines():
        if line.startswith(("%", "ENTRY")) and line.endswith("{"):
            name = line.split()[1 if line.startswith("ENTRY") else 0]
            comps[name] = []
        elif line.startswith("  ") and name:
            comps[name].append(line.strip().removeprefix("ROOT "))

    def pooled(op):
        """(opcode, called computation or None) of an instruction whose
        output holds a pool, else None."""
        rhs = op.partition(" = ")[2]
        code = re.search(r"\s([a-z][a-z0-9-]*)\(", rhs)
        if not any(s in rhs[:code.start()] for s in shapes):
            return None
        called = re.search(r"calls=(%[^,\s]+)", rhs)
        return code.group(1), called and called.group(1)

    fused = set(re.findall(r"calls=(%[^,\s]+)", hlo))
    in_place = {"parameter", "tuple", "get-tuple-element", "bitcast",
                "while"}
    bad = []
    for name, ops in comps.items():
        if name in fused:
            continue
        for op in ops:
            kind = pooled(op)
            if kind is None or kind[0] in in_place:
                continue
            if kind[0] == "fusion" and all(
                    p is None or p[0] in in_place | {"dynamic-update-slice"}
                    for p in map(pooled, comps[kind[1]])):
                continue
            bad.append(op.split(" = ")[0] + " " + kind[0])
    return bad


@pytest.mark.parametrize("step", ["decode", "chunk256"])
def test_serving_steps_update_kv_pools_in_place(one_chip, step):
    """mistral-nemo-12b at depth 8 (two scanned groups of four layers), the
    chip benchmark's engine: 32 slots, 4097 blocks of 16 tokens, the state
    donated.  The compiled decode step and 256-token chunk step make no
    whole KV pool: every layer writes its new tokens into the pool in place
    (no copy, allocation, relayout or scatter of a pool), so the step's
    temporaries hold no second pool set (4.84 GB when the pools were
    scanned), and the benchmark still reads each layer's attention kernel as
    flash_decode."""
    import dataclasses

    from repro import configs
    from repro.launch import steps
    from repro.models import model as M

    cfg = dataclasses.replace(configs.get("mistral-nemo-12b"), n_layers=8)
    slots, nb, bs, width = 32, 4097, 16, 256
    params = jax.eval_shape(lambda: M.init_model(jax.random.PRNGKey(0), cfg))
    state = jax.eval_shape(lambda: M.init_paged_decode_state(
        cfg, slots, num_blocks=nb, block_size=bs, max_blocks_per_slot=width))
    if step == "decode":
        make, extra = steps.make_paged_serve_step, [
            (jnp.int32, (slots, 1)), (jnp.bool_, (slots,))]
    else:
        make, extra = steps.make_prefill_chunk_step, [
            (jnp.int32, (1, 256)), (jnp.int32, ())]
    leaves, tree = jax.tree_util.tree_flatten((params, state))
    n_params = len(jax.tree_util.tree_leaves(params))

    def run(*flat):
        p, st = jax.tree_util.tree_unflatten(tree, flat[:len(leaves)])
        return make(cfg)(p, st, *flat[len(leaves):])

    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            for a in leaves] + [
        jax.ShapeDtypeStruct(s, d, sharding=one_chip) for d, s in extra]
    prev = ops.get_default_backend()
    ops.set_default_backend("pallas")
    try:
        with fd.decode_backend("flash"):
            compiled = jax.jit(run, donate_argnums=tuple(
                range(n_params, len(leaves)))).lower(*args).compile()
    finally:
        ops.set_default_backend(prev)
    G, H, D = cfg.n_groups, cfg.n_kv_heads, cfg.resolved_head_dim
    pools = [(nb, H, bs, D), (G, nb, H, bs, D), (G * nb, H, bs, D)]
    assert _whole_pool_ops(compiled.as_text(), pools) == []
    assert _kernels(compiled).count("flash_decode") == cfg.group_size
    assert compiled.memory_analysis().temp_size_in_bytes < 2.84e9


@pytest.mark.parametrize("rows,seq", [(64, 1), (1, 256)],
                         ids=["decode_64_rows", "chunk_256"])
def test_mamba2_mixer_compiles_at_granite_widths(one_chip, rows, seq):
    """granite-4.0-h-small's Mamba-2 mixer at its published widths (128
    heads of 64, state 128, d_inner 8192): the O(1) decode update over the
    cell's 64 slots and the SSD prefill of one 256-token chunk, with its
    projections on the GeMM kernel."""
    from repro import configs
    from repro.models import ssm

    cfg = configs.get("granite-4.0-h-small")
    params = jax.eval_shape(lambda: ssm.init_mamba2(jax.random.PRNGKey(0),
                                                     cfg))
    state = jax.eval_shape(lambda: ssm.init_mamba2_state(cfg, rows))
    leaves, tree = jax.tree_util.tree_flatten((params, state))

    def step(x, *flat):
        p, st = jax.tree_util.tree_unflatten(tree, flat)
        return ssm.mamba2_block(x, p, cfg, state=st)

    prev = ops.get_default_backend()
    ops.set_default_backend("pallas")
    try:
        _compile(one_chip, step, (jnp.bfloat16, (rows, seq, cfg.d_model)),
                 *[(a.dtype, a.shape) for a in leaves])
    finally:
        ops.set_default_backend(prev)
