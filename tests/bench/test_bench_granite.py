"""The granitemoehybrid architecture (arch/granite_hybrid.py) and its
configuration: the file loads and its cuts are checked, the weights are
laid out as the program's own init lays them, the work counts equal hand
counts, the decode dispatch span counts the recurrent state, and a smoke
run of the cell reads correct while its int8 control does not."""

import dataclasses
import types

import jax
import numpy as np
import pytest

import conftest
from benchlib import cells, drive, engine_spans as E, harness, system, work
from benchlib.traffic import Arrival

CONF = cells.load_config("granite-4.0-h-small-d10")
G = CONF.dims
ARCH = CONF.arch
V5E = work.PEAKS["TPU v5 lite"]

SMOKE_TYPES = ["mamba", "mamba", "attention", "mamba"]
SMOKE_GRANITE = dict(
    {k: v for k, v in CONF.raw.items() if k not in ("name", "engine")},
    name="smoke-granite", repo_config="granite-4.0-h-small-smoke",
    published={"num_hidden_layers": 8, "layer_types": SMOKE_TYPES * 2,
               "num_local_experts": 8},
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, vocab_size=256, num_hidden_layers=4,
    layer_types=SMOKE_TYPES, mamba_n_heads=8, mamba_d_head=16,
    mamba_d_state=16, mamba_chunk_size=8, num_local_experts=4,
    num_experts_per_tok=3, intermediate_size=32,
    shared_intermediate_size=64,
    engine={"slots": 4, "max_seq": 256, "block_size": 16, "max_chunk": 32},
    check={"max_served_gap": 0.05, "tokens": 160},
)


@pytest.fixture(autouse=True)
def smoke_granite_arch(monkeypatch):
    """The smoke configuration's repository config: the program's smoke
    preset of granite, under a name of its own."""
    from repro import configs

    get = configs.get
    monkeypatch.setattr(configs, "get", lambda name: (
        configs.get_smoke("granite-4.0-h-small")
        if name == SMOKE_GRANITE["repo_config"] else get(name)))


def _write(chip, raw):
    import json
    import os

    with open(os.path.join(chip, "configs", raw["name"] + ".json"), "w") as f:
        json.dump(raw, f)


def test_configuration_loads_as_published():
    assert G.kinds == ("mamba",) * 5 + ("attn",) + ("mamba",) * 4
    assert (G.n_mamba, G.n_attn, G.n_experts, G.held, G.top_k) == \
        (9, 1, 72, 9, 10)
    assert (G.d_inner, G.conv_dim, G.m_heads * G.m_head_dim) == \
        (8192, 8448, 8192)
    assert G.head_dim == 128 and G.vocab == 100352
    cfg = ARCH.program_config(CONF)
    assert cfg.layer_kinds() == G.kinds
    assert (cfg.moe.num_experts, cfg.moe.held, cfg.moe.first_expert) == \
        (72, 9, 0)
    assert cfg.attention_multiplier == 0.0078125 and cfg.nope


@pytest.mark.parametrize("key, value", [("mamba_d_state", 64),
                                        ("shared_intermediate_size", 768),
                                        ("num_experts_per_tok", 8)])
def test_a_cut_width_is_refused(tmp_path, key, value):
    _, chip = conftest.make_checkout(tmp_path)
    raw = dict(CONF.raw, name="cut", reduced=CONF.raw["reduced"] + [key],
               published=dict(CONF.raw["published"], **{key: CONF.raw[key]}),
               **{key: value})
    _write(chip, raw)
    with pytest.raises(cells.CellError, match="cuts widths"):
        cells.load_config("cut", chip)
    _write(chip, dict(raw, reduced=CONF.raw["reduced"]))
    with pytest.raises(cells.CellError, match=f"width {key!r}"):
        cells.load_config("cut", chip)


def test_program_tree_matches_the_programs_init(tmp_path):
    """At the published widths (shapes only) and at smoke size (drawn)."""
    from repro.models import model as M

    cfg = ARCH.program_config(CONF)
    want = jax.eval_shape(lambda: M.init_model(jax.random.PRNGKey(0), cfg))
    got = jax.eval_shape(lambda k: ARCH.program_tree(k, G, cfg),
                         jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    # Beside the parameters the program counts: two norms a layer and the
    # final norm.
    n = sum(a.size for a in jax.tree_util.tree_leaves(got))
    assert n == cfg.param_count() + 10 * 2 * 4096 + 4096
    _, chip = conftest.make_checkout(tmp_path)
    _write(chip, SMOKE_GRANITE)
    conf = cells.load_config("smoke-granite", chip)
    params = system.make_params(3, conf, conf.arch.program_config(conf))
    assert params["blocks"]["sub0"]["ffn"]["w_gate"].shape == (1, 4, 64, 32)


def test_experts_are_drawn_one_by_one():
    """Expert e's numbers do not depend on how many experts the chip holds,
    so the shares of all chips are slices of the uncut layer."""
    key = jax.random.PRNGKey(4)
    nine = np.asarray(ARCH._experts(key, (9, 64, 32)))
    three = np.asarray(ARCH._experts(key, (3, 64, 32)))
    assert np.array_equal(nine[:3], three)
    assert abs(float(nine.std()) - 64 ** -0.5) < 0.01


# Hand counts from the published widths (10 layers: 9 Mamba-2, 1 attention):
#   mamba projections  4096*16768 + 8192*4096             = 102,236,160
#   attention          4096*128*(32+16) + 32*128*4096     =  41,943,040
#   one expert         3*4096*768                         =   9,437,184
#   shared expert      3*4096*1536                        =  18,874,368
#   router             4096*72                            =     294,912
#   recurrence         5*128*64*128 + 2*4*8448            =   5,310,464
#   head               2*4096*100352                      = 822,083,584
MAMBA, ATTN, EXPERT, SHARED, ROUTER = (102_236_160, 41_943_040, 9_437_184,
                                       18_874_368, 294_912)
SSM, HEAD = 5_310_464, 822_083_584
# One token: 9 (2 MAMBA + SSM) + 2 ATTN + 10 * 2 (ROUTER + SHARED + 1.25
# EXPERT), the held experts' share of its top-10 being 10 * 9 / 72 = 1.25.
TOKEN = (9 * (2 * MAMBA + SSM) + 2 * ATTN
         + 10 * 2 * (ROUTER + SHARED + 1.25 * EXPERT))


def test_param_counts():
    assert (G.mamba_params, G.attn_params, G.expert_params,
            G.shared_params, G.router_params) == (MAMBA, ATTN, EXPERT,
                                                 SHARED, ROUTER)
    assert ARCH.ssm_flops(G) == SSM and ARCH.head_flops(G) == HEAD
    assert ARCH.token_flops(G) == TOKEN


def test_prefill_chunk_flops():
    # Tokens at positions 1024..1279 attend over 1025..1280 keys.
    keys = sum(range(1025, 1281))
    want = TOKEN * 256 + 4 * 32 * 128 * keys + HEAD
    assert ARCH.prefill_chunk_flops(G, 1024, 256) == want


def test_decode_flops_of_a_64_row_step():
    ctx = list(range(1000, 1064))
    want = (TOKEN + HEAD) * 64 + 4 * 32 * 128 * sum(ctx)
    assert ARCH.decode_flops(G, ctx) == want
    assert ARCH.decode_flops(G, []) == 0


def test_step_gemms_of_a_64_row_step():
    shapes = ARCH.step_gemms(G, 64, 64)
    # 64 rows x 10 pairs reach all 9 held experts, ceil(640 / 72) = 9 rows
    # each.
    assert ARCH.experts_touched(G, 64) == 9 and ARCH.expert_rows(G, 64) == 9
    assert len(shapes) == 9 * (2 + 4 + 27) + (4 + 4 + 27) + 1
    assert sum(k * n for _, k, n in shapes) == (
        9 * MAMBA + ATTN + 10 * (ROUTER + SHARED + 9 * EXPERT) + HEAD // 2)
    assert (9, 4096, 768) in shapes and shapes[-1] == (64, 4096, 100352)
    assert ARCH.experts_touched(G, 1) == 9 and ARCH.expert_rows(G, 1) == 1


def test_decode_least_times_of_a_64_row_step():
    state = 4 * 128 * 64 * 128 + 2 * 3 * 8448           # one slot, one layer
    mixer = 2 * (MAMBA + 4 * 8448 + 8448 + 8192) + 4 * 3 * 128
    want = 9 * (2 * 64 * state + mixer) / V5E.hbm_bw
    assert ARCH.decode_mamba_least_s(G, 64, V5E) == pytest.approx(want)
    assert want > 9 * 64 * (2 * MAMBA + SSM) / V5E.flops   # bytes bind
    moe = 10 * 2 * (9 * EXPERT + SHARED + ROUTER) / V5E.hbm_bw
    assert ARCH.decode_moe_least_s(G, 64, V5E) == pytest.approx(moe)
    assert ARCH.state_bytes_per_slot(G) == 9 * state == 38_204_928


def test_program_state_bytes_equal_the_work_count():
    """The program's recurrent state per slot at the published widths is
    what the work counts read and write per row."""
    from repro.models import model as M
    from repro.serving.kv_cache import PagedKVCache

    cfg = ARCH.program_config(CONF)
    st = jax.eval_shape(lambda: M.init_paged_decode_state(
        cfg, 2, num_blocks=17, block_size=16, max_blocks_per_slot=4))
    per_slot = sum(a.size * a.dtype.itemsize // 2
                   for c in st.caches if not isinstance(c, PagedKVCache)
                   for a in jax.tree_util.tree_leaves(c))
    assert per_slot == ARCH.state_bytes_per_slot(G)


@pytest.fixture(scope="module")
def smoke_granite(tmp_path_factory):
    offline = dict(conftest.SMOKE_OFFLINE, requests=16)
    return conftest.make_checkout(tmp_path_factory.mktemp("granite"),
                                  SMOKE_GRANITE, offline,
                                  cell="smoke-granite-offline",
                                  like="granite-reason-offline")


def _run(smoke, *extra):
    root, chip = smoke
    args = harness.parse(["--workload", "smoke-granite-offline", "--seed",
                          str(2**31 + 91), "--seconds", "1.5", "--trace",
                          "0", *extra])
    return harness.run(args, root=root, chip_dir=chip, require_chip=False)


def test_smoke_run_is_correct(smoke_granite):
    res = _run(smoke_granite)
    assert res["correct"] is True, res["check"]
    assert set(res["metrics"]) == {"out_tok_s", "setup_s"}
    assert res["check"]["served_tokens"]["value"] >= 160
    assert res["check"]["max_served_gap"]["value"] < 1e-3


@pytest.mark.parametrize("kind, layer", [("mamba", 0), ("attn", 2)])
def test_reference_layer_equals_the_programs(tmp_path, kind, layer):
    """One layer of the reference, float32 at HIGHEST, against the
    program's block on the same weights in float32: they agree to float32
    rounding over a few products (1e-5 of the layer's output); the int8
    control's layer (int8 products, K/V, conv inputs and state) departs by
    at least a hundred times that.  (At smoke size the control flips too
    few of 256 logits to fail a run, so the layer is where it shows.)"""
    from repro.models import blocks

    _, chip = conftest.make_checkout(tmp_path)
    _write(chip, SMOKE_GRANITE)
    conf = cells.load_config("smoke-granite", chip)
    m = conf.dims
    cfg = dataclasses.replace(conf.arch.program_config(conf), dtype="float32")
    key = jax.random.PRNGKey(0)
    tree = jax.tree_util.tree_map(
        lambda a: a.astype(np.float32),
        conf.arch.program_tree(key, m, cfg))["blocks"][f"sub{layer}"]
    p = jax.tree_util.tree_map(lambda a: a[0], tree)
    w = conf.arch._layer_weights(key, layer, m=m, kind=kind)
    T = 1024                 # the reference's attention works in 512-blocks
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (T, 64)))
    mixer = {"mamba": conf.arch._mamba, "attn": conf.arch._attn}[kind]

    def ref(precision):
        y = mixer(x, w, m=m, precision=precision)
        return np.asarray(conf.arch._moe(y, w, m=m, precision=precision))

    got, _ = blocks.apply_block(x[None], p, cfg, kind,
                                positions=np.arange(T)[None])
    got = np.asarray(got[0])
    want = ref("float32")
    scale = float(np.abs(want - x).max())
    assert np.abs(got - want).max() < 1e-5 * scale * 10
    assert np.abs(ref("int8") - want).max() > 1e-3 * scale


def test_decode_dispatch_counts_the_recurrent_state(tmp_path, monkeypatch):
    """Each decode tick's engine.dispatch carries state_bytes: its active
    rows times one slot's state over the layers (three Mamba-2 layers of
    the smoke preset: h 8*16*16 float32 and a conv tail 3*(128+2*16) in the
    served dtype)."""
    from repro import configs
    from repro.serving.engine import Engine

    cfg = dataclasses.replace(configs.get_smoke("granite-4.0-h-small"),
                              dtype="bfloat16")
    per_slot = 3 * (4 * 8 * 16 * 16 + 2 * 3 * 160)
    eng = Engine(cfg, slots=4, max_seq=128, block_size=16, max_chunk=32)
    assert eng._state_bytes_per_slot == per_slot
    eng.warmup()
    monkeypatch.setattr(cells, "ROOT", str(tmp_path))
    trace_dir = str(tmp_path / ".bench_trace" / "smoke-granite")
    rng = np.random.default_rng(5)
    arrivals = [Arrival(t=0.05 * i, prompt=rng.integers(0, 250, size=n)
                        .astype(np.int32), max_new=m)
                for i, (n, m) in enumerate([(40, 30), (9, 40), (20, 20),
                                            (33, 45)] * 2)]
    win = drive.Driver(eng, arrivals, system.request_spec).run(
        warm_in_s=0.0, seconds=1.0, trace_s=0.6,
        profiler=(lambda: jax.profiler.start_trace(trace_dir),
                  jax.profiler.stop_trace))
    r = E.reduce(E.load_dir(trace_dir))
    logged = win.ticks[win.trace_tick0:win.trace_tick1]
    assert len(r.ticks) == len(logged)
    decodes = 0
    for got, want in zip(r.ticks, logged):
        if want.kind == "decode":
            decodes += 1
            assert got.meta == {"rows": len(want.contexts),
                                "ctx_tokens": sum(want.contexts),
                                "state_bytes": len(want.contexts) * per_slot}
    assert decodes > 0
    ctx = types.SimpleNamespace(trace=object(),
                                cell={"name": "smoke-granite"})
    assert E.reading(ctx) is not None
