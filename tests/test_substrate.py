"""Substrate tests: optimizer, train step, data pipeline, checkpointing,
fault-tolerant driver, straggler detection, compression, serving."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.checkpoint import CheckpointManager, restore, save
from repro.checkpoint.manager import latest_step
from repro.config import RunConfig, ShapeConfig
from repro.configs import get_reduced
from repro.data import PrefetchLoader, SyntheticLMStream
from repro.distributed.compression import dequantize_int8, quantize_int8
from repro.models import init_model_params
from repro.optim import (clip_by_global_norm, init_opt_state,
                         lr_schedule)
from repro.runtime import FaultTolerantTrainer, InjectedFault, StragglerMonitor
from repro.serve import ServeEngine
from repro.train import train_step

RC = RunConfig(remat=False, dtype="float32", lr=1e-2, warmup_steps=5,
               total_steps=100)
KEY = jax.random.PRNGKey(0)


def _cfg():
    return get_reduced("phi3-mini-3.8b")


def _batch(cfg, B=4, S=16, seed=0):
    s = SyntheticLMStream(cfg.vocab, S, B, seed=seed)
    return {k: jnp.asarray(v) for k, v in s.batch_at(0).items()}


# --- optimizer --------------------------------------------------------------

def test_lr_schedule_shape():
    rc = RunConfig(lr=1e-3, warmup_steps=10, total_steps=100)
    lrs = [float(lr_schedule(jnp.asarray(s), rc)) for s in range(0, 101, 10)]
    assert lrs[0] < lrs[1]                      # warmup rises
    assert lrs[-1] < lrs[2]                     # cosine decays
    assert abs(lrs[1] - 1e-3) < 1e-4            # peak at end of warmup


def test_grad_clip():
    g = {"a": jnp.full((4,), 10.0), "b": jnp.full((4,), -10.0)}
    clipped, norm = clip_by_global_norm(g, 1.0)
    total = jnp.sqrt(sum(jnp.sum(x ** 2) for x in
                         jax.tree_util.tree_leaves(clipped)))
    assert abs(float(total) - 1.0) < 1e-5
    assert float(norm) > 1.0


def test_train_loss_decreases():
    cfg = _cfg()
    params = init_model_params(KEY, cfg)
    opt = init_opt_state(params)
    batch = _batch(cfg)
    step = jax.jit(lambda p, o, b: train_step(p, o, b, cfg, RC))
    first = None
    for _ in range(30):
        params, opt, metrics = step(params, opt, batch)
        if first is None:
            first = float(metrics["loss"])
    assert float(metrics["loss"]) < first * 0.8


def test_microbatch_accumulation_matches_full_batch():
    cfg = _cfg()
    params = init_model_params(KEY, cfg)
    batch = _batch(cfg, B=4)
    rc_full = RunConfig(remat=False, dtype="float32")
    rc_mb = RunConfig(remat=False, dtype="float32", microbatch=2)
    from repro.train.step import _grads
    g1, _ = _grads(params, batch, cfg, rc_full)
    g2, _ = _grads(params, batch, cfg, rc_mb)
    flat1 = jax.tree_util.tree_leaves(g1)
    flat2 = jax.tree_util.tree_leaves(g2)
    for a, b in zip(flat1, flat2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3)


# --- data -------------------------------------------------------------------

def test_stream_deterministic_and_seekable():
    s = SyntheticLMStream(100, 16, 4, seed=7)
    a = s.batch_at(12)
    b = s.batch_at(12)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = s.batch_at(13)
    assert not np.array_equal(a["tokens"], c["tokens"])
    # labels are next-token shifted
    full_a = np.concatenate([a["tokens"], a["labels"][:, -1:]], axis=1)
    np.testing.assert_array_equal(full_a[:, 1:], a["labels"])


def test_stream_dp_sharding_partitions_batch():
    full = SyntheticLMStream(100, 8, 4, seed=3)
    parts = [SyntheticLMStream(100, 8, 4, seed=3, dp_rank=r, dp_size=2)
             for r in range(2)]
    b = [p.batch_at(5)["tokens"] for p in parts]
    assert b[0].shape == (2, 8)
    assert not np.array_equal(b[0], b[1])      # ranks see different data


def test_prefetch_loader_orders_batches():
    s = SyntheticLMStream(100, 8, 2, seed=1)
    loader = PrefetchLoader(s, start_step=3, depth=2)
    try:
        got = loader.get()
        np.testing.assert_array_equal(got["tokens"], s.batch_at(3)["tokens"])
        got2 = loader.get()
        np.testing.assert_array_equal(got2["tokens"], s.batch_at(4)["tokens"])
    finally:
        loader.close()


# --- compression ------------------------------------------------------------

@given(st.integers(0, 2 ** 31 - 1))
@settings(max_examples=20, deadline=None)
def test_int8_quantization_unbiased(seed):
    key = jax.random.PRNGKey(seed)
    g = jax.random.normal(key, (64,)) * 0.37
    qs = [dequantize_int8(*quantize_int8(jax.random.fold_in(key, i), g))
          for i in range(64)]
    mean = jnp.stack(qs).mean(0)
    scale = float(jnp.max(jnp.abs(g))) / 127.0
    np.testing.assert_allclose(np.asarray(mean), np.asarray(g),
                               atol=scale * 0.6)
    # single round trip error bounded by one quantization step
    assert float(jnp.max(jnp.abs(qs[0] - g))) <= scale + 1e-6


# --- checkpointing ----------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    state = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
             "b": {"c": jnp.asarray(3)}}
    save(str(tmp_path), 7, state, extra={"data_step": 7})
    step, back, extra = restore(str(tmp_path), state)
    assert step == 7 and extra["data_step"] == 7
    np.testing.assert_array_equal(np.asarray(back["a"]), np.asarray(state["a"]))


def test_checkpoint_manager_async_keep_k(tmp_path):
    m = CheckpointManager(str(tmp_path), keep=2)
    for s in (10, 20, 30, 40):
        m.save_async(s, {"x": jnp.asarray([s])})
    m.wait()
    m.close()
    steps = sorted(int(d.split("_")[1]) for d in os.listdir(tmp_path)
                   if d.startswith("step_"))
    assert steps == [30, 40]
    assert latest_step(str(tmp_path)) == 40


def test_checkpoint_atomicity_no_tmp_left(tmp_path):
    save(str(tmp_path), 1, {"x": jnp.ones(3)})
    assert not [d for d in os.listdir(tmp_path) if d.startswith(".tmp")]


# --- fault tolerance ---------------------------------------------------------

def test_trainer_recovers_from_injected_fault(tmp_path):
    cfg = _cfg()
    shape = ShapeConfig("tiny", 16, 4, "train")
    params = init_model_params(KEY, cfg)
    faults = {17}

    def fault_hook(step):
        if step in faults:
            faults.discard(step)
            raise InjectedFault(f"device loss @ {step}")

    def mesh_factory():
        from repro.launch.mesh import make_local_mesh
        return make_local_mesh(1, 1)

    tr = FaultTolerantTrainer(cfg, shape, RC, mesh_factory, str(tmp_path),
                              ckpt_every=10, fault_hook=fault_hook)
    out = tr.run(params, num_steps=25)
    assert out["restarts"] == 1
    assert out["step"] == 25
    # the rerun re-executed steps 10..16 after restoring the step-10 ckpt
    steps_seen = [s for s, _ in out["metrics"]]
    assert steps_seen.count(12) == 2


def test_trainer_resume_determinism(tmp_path):
    """Same data at a given step whether or not a restart happened."""
    s = SyntheticLMStream(64, 8, 2, seed=0)
    np.testing.assert_array_equal(s.batch_at(11)["tokens"],
                                  s.batch_at(11)["tokens"])


# --- straggler --------------------------------------------------------------

def test_straggler_detection():
    events = []
    mon = StragglerMonitor(window=20, threshold=4.0, min_samples=10,
                           on_straggler=lambda s, t, z: events.append(s))
    for i in range(30):
        mon.record(i, 0.10 + 0.001 * (i % 3))
    mon.record(30, 0.50)                       # 5x median
    assert events == [30]
    assert not mon.record(31, 0.101)           # baseline unpolluted


def test_heartbeat():
    from repro.runtime.straggler import Heartbeat
    hb = Heartbeat(["h0", "h1"], timeout=10.0)
    hb.beat("h0", 100.0)
    hb.beat("h1", 95.0)
    assert hb.dead(106.0) == ["h1"]


# --- serving ----------------------------------------------------------------

def test_serve_engine_batched_requests():
    cfg = _cfg()
    params = init_model_params(KEY, cfg)
    eng = ServeEngine(params, cfg, RC, batch_slots=2, max_len=64)
    r1 = eng.submit([1, 2, 3], max_new=4)
    r2 = eng.submit([4, 5], max_new=4)
    done = eng.run()
    assert set(done) == {r1, r2}
    for r in done.values():
        assert len(r.generated) == 4
        assert all(0 <= t < cfg.vocab for t in r.generated)


def test_heartbeat_no_false_dead_on_startup():
    """A monitor created at a large wall-clock time must give every host a
    full timeout window before declaring it dead — the last-beat table is
    seeded from the start time, not an implicit 0.0."""
    from repro.runtime.straggler import Heartbeat
    hb = Heartbeat(["h0", "h1"], timeout=10.0, start=1000.0)
    assert hb.dead(1005.0) == []            # nobody has beaten yet: alive
    hb.beat("h0", 1009.0)
    assert hb.dead(1011.0) == ["h1"]        # h1 never beat, window expired
    assert hb.dead(1030.0) == ["h0", "h1"]  # h0's beat aged out too


def test_serve_engine_second_wave_matches_fresh_engine():
    """Readmission must not reuse stale KV state: a request served in the
    second wave of a 2-slot engine generates the same tokens as the same
    request on a fresh engine."""
    cfg = _cfg()
    params = init_model_params(KEY, cfg)
    prompt, max_new = [7, 3, 9, 1], 5

    eng = ServeEngine(params, cfg, RC, batch_slots=2, max_len=64)
    eng.submit([1, 2, 3], max_new=4)
    eng.submit([4, 5, 6], max_new=4)
    eng.run()                               # wave 1 drains all slots
    rid = eng.submit(prompt, max_new=max_new)
    second_wave = eng.run()[rid].generated

    fresh = ServeEngine(params, cfg, RC, batch_slots=2, max_len=64)
    rid_f = fresh.submit(prompt, max_new=max_new)
    assert second_wave == fresh.run()[rid_f].generated


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/jax-cache"])
def test_compile_cache_dir(monkeypatch, env_dir):
    """The entry points keep compiled programs in <repo>/.jax_cache unless
    JAX_COMPILATION_CACHE_DIR names a directory, which JAX reads itself."""
    from repro.launch import compile_cache
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *kv: updates.append(kv))
    compile_cache.enable_compile_cache()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = ([] if env_dir else
            [("jax_compilation_cache_dir", os.path.join(root, ".jax_cache"))])
    assert updates == want
