"""Mini dry-run in a subprocess: the full lower → compile → analyse path on
an 8-fake-device mesh with a reduced config (the 512-device production runs
live in launch/dryrun.py; this guards the machinery in CI time)."""
import os
import subprocess
import sys
import textwrap


def _run(code: str, timeout=900):
    return subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        cwd=os.path.join(os.path.dirname(__file__), ".."),
        capture_output=True, text=True, timeout=timeout)


def test_mini_dryrun_train_and_decode():
    r = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, "src")
        import jax
        from repro.configs import get_config, input_specs, ShapeCell
        from repro.launch.mesh import make_mesh
        from repro.launch.hlo_walker import module_cost
        from repro.models import api
        from repro.train import make_train_step, make_serve_step
        from repro.train.step import opt_state_shapes

        mesh = make_mesh((2, 4), ("data", "model"))
        cfg = get_config("granite-3-8b", smoke=True)
        cell = ShapeCell("t", "train", 128, 8)
        batch = input_specs(cfg, cell)
        bundle = make_train_step(cfg, mesh, batch, n_micro=2, loss_chunk=64)
        fn = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                     out_shardings=bundle.out_shardings)
        with jax.set_mesh(mesh):
            lowered = fn.lower(api.param_shapes(cfg), opt_state_shapes(cfg),
                               batch)
        comp = lowered.compile()
        mem = comp.memory_analysis()
        assert mem.argument_size_in_bytes > 0
        cost = module_cost(comp.as_text(), 8)
        assert cost.flops > 1e6, cost.flops
        assert cost.coll_bytes > 0            # grad all-reduce exists

        # decode bundle on the same mesh
        b2 = make_serve_step(cfg, mesh, batch_size=8, seq_len=256)
        cache = api.cache_shapes(cfg, 8, 256)
        import jax.numpy as jnp
        tok = jax.ShapeDtypeStruct((8, 1), jnp.int32)
        fn2 = jax.jit(b2.fn, in_shardings=b2.in_shardings,
                      out_shardings=b2.out_shardings)
        with jax.set_mesh(mesh):
            comp2 = fn2.lower(api.param_shapes(cfg), cache, tok).compile()
        assert comp2.memory_analysis().argument_size_in_bytes > 0
        print("OK")
    """)
    assert "OK" in r.stdout, (r.stdout[-1000:], r.stderr[-3000:])


def test_multipod_mesh_axes_subprocess():
    r = _run("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, "src")
        import jax
        from repro.launch.mesh import make_mesh
        from repro.models.sharding import ShardingPolicy
        from repro.configs import get_config
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        cfg = get_config("granite-3-8b", smoke=True)
        pol = ShardingPolicy(cfg, mesh, "train")
        spec = pol.batch_spec("tokens", (8, 128))
        assert spec[0] == ("pod", "data"), spec   # batch spans the pod axis
        print("OK")
    """)
    assert "OK" in r.stdout, (r.stdout[-500:], r.stderr[-3000:])
