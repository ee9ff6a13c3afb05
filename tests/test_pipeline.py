"""GPipe pipeline parallelism: subprocess test on a 4-device fake mesh."""

import json
import os
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax, jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from repro.parallel.pipeline import gpipe, split_stages

    mesh = make_mesh((4,), ("pod",))
    G, d = 8, 16                     # 8 layer groups -> 4 stages of 2
    key = jax.random.PRNGKey(0)
    Ws = jax.random.normal(key, (G, d, d)) * (d ** -0.5)

    def group_fn(W, x):              # one "layer group": x -> tanh(x @ W)
        return jnp.tanh(x @ W)

    def stage_fn(stage_params, x):   # stage = its slice of groups, in order
        def body(h, W):
            return group_fn(W, h), None
        h, _ = jax.lax.scan(body, x, stage_params)
        return h

    n_micro = 3
    x = jax.random.normal(jax.random.PRNGKey(1), (n_micro, 5, d))

    # sequential reference
    def seq_forward(Ws, xb):
        def body(h, W):
            return group_fn(W, h), None
        return jax.lax.scan(body, xb, Ws)[0]
    ref = jax.vmap(lambda xb: seq_forward(Ws, xb))(x)

    piped = gpipe(stage_fn, mesh, axis="pod", n_micro=n_micro)
    stages = split_stages(Ws, 4)
    out = jax.jit(piped)(stages, x)
    err = float(jnp.max(jnp.abs(out - ref)))

    # gradients flow through the pipeline (ppermute transpose)
    def loss(stages, x):
        return jnp.sum(piped(stages, x) ** 2)
    g = jax.grad(loss)(stages, x)
    gnorm = float(sum(jnp.sum(jnp.abs(t)) for t in jax.tree_util.tree_leaves(g)))

    def seq_loss(Ws, x):
        return jnp.sum(jax.vmap(lambda xb: seq_forward(Ws, xb))(x) ** 2)
    g_ref = jax.grad(seq_loss)(Ws, x).reshape(4, 2, d, d)
    gerr = float(jnp.max(jnp.abs(g[0] if isinstance(g, tuple) else g) - 0) )
    import numpy as np
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=1e-4, atol=1e-5)
    print(json.dumps({"err": err, "gnorm": gnorm, "ok": True}))
""")


def test_gpipe_matches_sequential():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["ok"] and res["err"] < 1e-5 and res["gnorm"] > 0
