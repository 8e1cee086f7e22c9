"""Substrate tests: data determinism/resharding, checkpoint atomicity +
restart, gradient compression numerics, straggler policies, supervisor
restart loop, elastic fleet replanning."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def test_data_pipeline_deterministic_and_reshardable():
    from repro.data.pipeline import DataConfig, SyntheticLM
    cfg = DataConfig(vocab_size=512, seq_len=32, global_batch=16, seed=7)
    ds = SyntheticLM(cfg)
    a = ds.global_batch(3)
    b = ds.global_batch(3)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    # resharding invariance: 4 shards of the step == the global batch
    parts = [ds.shard_batch(3, s, 4)["tokens"] for s in range(4)]
    np.testing.assert_array_equal(np.concatenate(parts), a["tokens"])
    # different num_shards sees the same stream
    parts2 = [ds.shard_batch(3, s, 2)["tokens"] for s in range(2)]
    np.testing.assert_array_equal(np.concatenate(parts2), a["tokens"])
    # labels are next tokens
    np.testing.assert_array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])


def test_checkpoint_roundtrip_and_restart(tmp_path):
    from repro.checkpoint import checkpoint as ckpt
    tree = {"w": np.arange(12, dtype=np.float32).reshape(3, 4),
            "opt": {"m": np.ones(5), "step": np.asarray(7)}}
    ckpt.save(str(tmp_path), 10, tree, extra={"loss": 1.5})
    tree2 = {k: (jax.tree_util.tree_map(np.zeros_like, v) if isinstance(v, dict)
                 else np.zeros_like(v)) for k, v in tree.items()}
    step, loaded, extra = ckpt.load_latest(str(tmp_path), tree2)
    assert step == 10 and extra["loss"] == 1.5
    np.testing.assert_array_equal(loaded["w"], tree["w"])
    # newer checkpoint wins
    ckpt.save(str(tmp_path), 20, tree)
    step, _, _ = ckpt.load_latest(str(tmp_path), tree2)
    assert step == 20
    # uncommitted (partial) checkpoints are ignored
    os.makedirs(tmp_path / "step_00000030", exist_ok=True)
    step, _, _ = ckpt.load_latest(str(tmp_path), tree2)
    assert step == 20


def test_async_checkpointer(tmp_path):
    from repro.checkpoint.checkpoint import AsyncCheckpointer, load_latest
    ac = AsyncCheckpointer(str(tmp_path), keep=2)
    tree = {"w": np.ones((4, 4), np.float32)}
    for s in (1, 2, 3):
        ac.save(s, {"w": tree["w"] * s})
    ac.wait()
    step, loaded, _ = load_latest(str(tmp_path), tree)
    assert step == 3
    np.testing.assert_array_equal(loaded["w"], tree["w"] * 3)
    # GC kept only 2
    assert len([d for d in os.listdir(tmp_path) if d.startswith("step_")]) == 2


def test_grad_compression_error_feedback_converges():
    """Compressed channel with error feedback: the RUNNING SUM of
    dequantized grads tracks the running sum of true grads (unbiasedness)."""
    from repro.optim.grad_compress import compress_decompress
    rng = np.random.default_rng(0)
    g_true_sum = np.zeros(300, np.float32)
    g_seen_sum = np.zeros(300, np.float32)
    err = jnp.zeros(300, jnp.float32)
    for t in range(50):
        g = jnp.asarray(rng.normal(0, 1, 300), jnp.float32)
        deq, err = compress_decompress(g, err)
        g_true_sum += np.asarray(g)
        g_seen_sum += np.asarray(deq)
    # residual bounded by one quantization step, not growing with t
    resid = np.abs(g_true_sum - g_seen_sum).max()
    assert resid <= np.abs(np.asarray(err)).max() + 1e-5
    assert resid < 0.2


@pytest.mark.slow
def test_compressed_psum_multidevice_subprocess():
    """Real psum over 4 host devices in a child process (tests must not
    force device count in THIS process)."""
    import subprocess, sys, textwrap
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.optim.grad_compress import compressed_psum
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("data",))
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.normal(0, 1, (4, 256)), jnp.float32)
        err0 = jnp.zeros((4, 256), jnp.float32)
        def f(g, e):
            out, err = compressed_psum(g, e, "data")
            return out, err
        fm = jax.shard_map(f, mesh=mesh, in_specs=(P("data"), P("data")),
                           out_specs=(P("data"), P("data")),
                           check_vma=False)
        out, err = fm(g, err0)
        true = np.asarray(g).sum(0)
        got = np.asarray(out)[0]
        rel = np.abs(got - true).max() / (np.abs(true).max() + 1e-9)
        assert rel < 0.05, rel
        print("OK", rel)
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env={**os.environ, "PYTHONPATH": "src"},
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.dirname(os.path.abspath(__file__)))))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout


def test_straggler_policies():
    from repro.distributed.fault_tolerance import (StragglerMonitor,
                                                   simulate_step_times)
    rng = np.random.default_rng(1)
    mon_wait = StragglerMonitor(n_workers=16, policy="wait")
    mon_dead = StragglerMonitor(n_workers=16, policy="deadline")
    t_wait = t_dead = 0.0
    for _ in range(50):
        times = simulate_step_times(rng, 16, straggle_prob=0.08)
        t_wait += mon_wait.effective_step_time(times)
        t_dead += mon_dead.effective_step_time(times)
    # deadline policy must beat synchronous waiting under stragglers
    assert t_dead < t_wait
    plan = mon_dead.plan(np.array([1.0] * 15 + [50.0]))
    assert plan["included"].sum() == 15
    assert abs(plan["renorm"] - 16 / 15) < 1e-9


def test_supervisor_restart_loop(tmp_path):
    from repro.checkpoint import checkpoint as ckpt
    from repro.distributed.fault_tolerance import (SupervisorConfig,
                                                   TrainingSupervisor)
    state = {"w": np.zeros(4, np.float32)}
    fails = {"n": 0}

    def train_fn(start_step, num_shards):
        step = start_step
        while step < 60:
            step += 1
            state["w"] += 1.0
            if step % 20 == 0:
                ckpt.save(str(tmp_path), step, state)
            if step == 33 and fails["n"] == 0:
                fails["n"] += 1
                raise RuntimeError("host_down")
        return step

    sup = TrainingSupervisor(SupervisorConfig(), str(tmp_path))
    final = sup.run(train_fn, total_steps=60, initial_shards=4)
    assert final == 60
    assert sup.restarts == 1
    assert sup.events[0].step == 20  # resume point = last committed ckpt


def test_elastic_fleet_replans():
    from repro.core.workloads import JobSpec
    from repro.distributed.elastic import ElasticFleet
    job = JobSpec(name="train-104b", hlo_flops=2.5e16, hlo_bytes=1e14,
                  collective_bytes=5e12, bytes_per_device=8e9, devices=256,
                  step_budget_s=1.0)
    fleet = ElasticFleet(job, delta_max=64.0)
    plan = fleet.initial_plan()
    assert plan.total_chips >= 64          # compute demand needs real chips
    assert plan.cost_per_hour > 0
    # kill 30% of the fleet -> replan restores capacity
    failed = np.ceil(fleet.controller.x_current * 0.3)
    plan2 = fleet.replan_after_failure(failed)
    assert plan2.total_chips >= plan.total_chips * 0.6
    assert plan2.mesh_shape[1] == 16


@pytest.mark.slow
def test_pipeline_parallel_subprocess():
    """GPipe schedule on a 4-stage host-device mesh matches sequential."""
    import subprocess, sys, textwrap
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.pipeline_parallel import pipeline_apply
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("pipe",))
        rng = np.random.default_rng(0)
        n_stages, n_micro, mb, d = 4, 8, 2, 16
        Ws = jnp.asarray(rng.normal(0, 0.5, (n_stages, d, d)), jnp.float32)
        x = jnp.asarray(rng.normal(0, 1, (n_micro, mb, d)), jnp.float32)
        def stage(W, x):
            return jnp.tanh(x @ W)
        out = pipeline_apply(stage, Ws, x, mesh=mesh, n_stages=n_stages)
        # sequential reference
        ref = x
        for s in range(n_stages):
            ref = jnp.tanh(ref @ Ws[s])
        err = float(jnp.max(jnp.abs(out - ref)))
        assert err < 1e-5, err
        print("OK", err)
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env={**os.environ, "PYTHONPATH": "src"},
                       cwd=os.path.dirname(os.path.dirname(
                           os.path.dirname(os.path.abspath(__file__)))))
    assert r.returncode == 0, r.stderr[-2000:]
    assert "OK" in r.stdout
