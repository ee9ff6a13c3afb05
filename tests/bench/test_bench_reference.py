"""The reference's programs take shapes that repeat from run to run, so a
run finds them compiled whatever lengths it served: a request still
decoding at the close has a length of its own."""

import numpy as np

from benchlib import cells, harness, reference

DENSE = cells.arch_module("mistral")
DIMS = DENSE.Dims(name="tiny", n_layers=2, d_model=64, n_heads=4,
                  n_kv_heads=2, head_dim=16, d_ff=128, vocab=256,
                  norm_eps=1e-5, rope_theta=1e6, qk_norm=False,
                  dtype="bfloat16")


def _requests(rng, served):
    return [(rng.integers(0, DIMS.vocab, 40, dtype=np.int32),
             rng.integers(0, DIMS.vocab, n, dtype=np.int32)) for n in served]


def test_served_lengths_in_one_bucket_compile_once():
    rng = np.random.default_rng(0)
    DENSE.served_gaps(DIMS, 3, _requests(rng, [37]), control="int8")
    compiles = harness.CompileCounter()
    got = DENSE.served_gaps(DIMS, 3, _requests(rng, [90, 5]),
                            control="int8")
    assert compiles.n == 0
    assert [g.size for g in got["served"]] == [90, 5]
    assert [g.size for g in got["int8"]] == [90, 5]
    assert all(np.all(g >= 0) for g in got["served"] + got["int8"])


def test_row_buckets():
    assert [reference.row_bucket(n) for n in (1, 128, 129, 700, 3072)] == \
        [128, 128, 256, 1024, 4096]
