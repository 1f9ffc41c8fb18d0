"""Multi-device distribution drills on 8 fake host devices.

Each test spawns a subprocess (XLA device count is fixed at first jax init,
so the 8-device platform needs its own process) running a scenario script:

  * sharded train step on a (2, 4) ('data','model') mesh: loss decreases,
    params stay sharded;
  * int8 error-feedback gradient all-reduce via shard_map over a pod axis
    matches the dense all-reduce within tolerance;
  * elastic re-mesh: checkpoint saved from an 8-device mesh restores onto
    4- and 2-device meshes bit-identically.
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def run_scenario(code: str, timeout=600) -> dict:
    env = {**os.environ,
           "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": str(REPO / "src"),
           "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=timeout, cwd=REPO)
    assert r.returncode == 0, f"scenario failed:\n{r.stderr[-3000:]}"
    last = [l for l in r.stdout.strip().splitlines() if l.startswith("{")][-1]
    return json.loads(last)


@pytest.mark.slow
def test_sharded_train_step_8dev():
    out = run_scenario("""
        import json
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import smoke_config
        from repro.models import get_model
        from repro.parallel.logical import split_logical, values_of
        from repro.parallel.sharding import rules_for_mesh
        from repro.train.step import make_train_step
        from repro.optim.adamw import adamw_init
        from repro.optim.schedules import constant_lr
        from repro.data import DataConfig, SyntheticCorpus

        cfg = smoke_config('llama3.2-3b')
        api = get_model(cfg)
        mk = {'axis_types': (jax.sharding.AxisType.Auto,) * 2}
        mesh = jax.make_mesh((2, 4), ('data', 'model'), **mk)
        rules = rules_for_mesh(mesh)
        ltree = api.init_params(jax.random.PRNGKey(0))
        params, specs = split_logical(ltree, rules)
        shardings = jax.tree.map(lambda sp: NamedSharding(mesh, sp), specs)
        params = jax.device_put(params, shardings)
        opt = adamw_init(params)
        corpus = SyntheticCorpus(DataConfig(vocab=cfg.vocab, seq_len=32,
                                            global_batch=8))
        step = jax.jit(make_train_step(api, constant_lr(3e-3)),
                       donate_argnums=(0, 1))
        losses = []
        with mesh:
            for i in range(8):
                b = corpus.batch(i)
                batch = {k: jnp.asarray(v) for k, v in b.items()}
                params, opt, m = step(params, opt, batch)
                losses.append(float(m['loss']))
        w = params['blocks']['attn']['wq']
        print(json.dumps({
            'first': losses[0], 'last': losses[-1],
            'n_shards': len(w.sharding.device_set),
            'finite': all(l == l for l in losses)}))
    """)
    assert out["finite"]
    assert out["last"] < out["first"]
    assert out["n_shards"] == 8


@pytest.mark.slow
def test_compressed_pod_allreduce_matches_dense():
    out = run_scenario("""
        import json
        import jax, jax.numpy as jnp
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from jax.experimental.shard_map import shard_map
        from repro.optim.compression import compressed_psum_tree, ef_state_init

        mk = {'axis_types': (jax.sharding.AxisType.Auto,) * 2}
        mesh = jax.make_mesh((2, 4), ('pod', 'data'), **mk)
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.normal(size=(8, 64)) * 0.01, jnp.float32)
        grads = {'w': g}
        err = ef_state_init(grads)

        def f(gr, er):
            return compressed_psum_tree(gr, er, 'pod')

        spec = {'w': P('pod', None)}
        fn = shard_map(f, mesh=mesh, in_specs=(spec, spec),
                       out_specs=(spec, spec))
        mean_g, new_err = fn(grads, err)
        # dense reference: mean over the pod axis groups
        dense = (np.asarray(g).reshape(2, 4, 64).mean(0))
        got = np.asarray(mean_g['w']).reshape(2, 4, 64)
        err_max = float(np.abs(got - dense[None]).max())
        print(json.dumps({'err_max': err_max,
                          'scale': float(np.abs(dense).max())}))
    """)
    assert out["err_max"] <= max(1e-4, out["scale"] * 0.02)


@pytest.mark.slow
def test_elastic_remesh_roundtrip(tmp_path):
    out = run_scenario(f"""
        import json
        import jax, jax.numpy as jnp
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.ckpt import CheckpointManager, reshard_tree

        tree = {{'w': jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
                'b': jnp.ones((8,))}}
        spec = {{'w': P('data', 'model'), 'b': P('data')}}

        mk = {{'axis_types': (jax.sharding.AxisType.Auto,) * 2}}
        m8 = jax.make_mesh((4, 2), ('data', 'model'), **mk)
        placed = reshard_tree(tree, m8, spec)
        mgr = CheckpointManager(r'{tmp_path}', keep=2)
        mgr.save(1, placed)

        # "failure": restart on a smaller mesh (4 devices)
        devs = jax.devices()[:4]
        import numpy as _np
        m4 = jax.sharding.Mesh(_np.array(devs).reshape(2, 2),
                               ('data', 'model'))
        restored, step = mgr.restore(placed)
        placed4 = reshard_tree(restored, m4, spec)
        same = bool((_np.asarray(placed4['w']) ==
                     _np.asarray(tree['w'])).all())
        print(json.dumps({{'same': same, 'step': step,
                          'n_dev': len(placed4['w'].sharding.device_set)}}))
    """)
    assert out["same"] and out["step"] == 1 and out["n_dev"] == 4
