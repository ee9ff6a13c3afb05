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
D=128), float and int8 KV pools, in the decode and prefill-chunk steps.

Nothing runs: a pass here is a compile, not a chip run.  Only one process
may load the TPU library at a time, so the topology is described inside a
module fixture (never at import) and every case compiles in this process.
"""

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
    """64 table columns of 16-token blocks (max_seq 1024) per slot: the
    serving shape whose analytic tuner winner is num_splits=16, in the
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
