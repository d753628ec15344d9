"""Serve-path tests: admission backpressure, continuous vs static (wave)
slot refill, straggler-aware host dispatch, SLO accounting on the
virtual-time simulation, the live engine's continuous-batching equivalence
(a mid-run admitted request decodes the same tokens as on a fresh engine),
chunked prefill matching the token path on mixed-phase batches, pinned-traffic
operating points, and the engine's host-clock stamps and step log."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import RunConfig
from repro.configs import get_reduced
from repro.models import init_model_params
from repro.serve import (AdmissionControl, AdmissionError,
                         ContinuousScheduler, HostDispatch, ServeEngine,
                         ServeSLO, StepCostModel, TraceRequest,
                         TrafficEstimator, simulate_serve)

RC = RunConfig(remat=False, dtype="float32")
KEY = jax.random.PRNGKey(0)

#: flat cost model for scheduler-level tests: no machine-model dependency,
#: round numbers make the virtual-time arithmetic auditable by hand
FLAT = StepCostModel(cycles_decode_token=10.0, energy_decode_token=5.0,
                     cycles_prefill_token=2.5, energy_prefill_token=1.25,
                     overhead_cycles=20.0, source="flat-test")


def _cfg():
    return get_reduced("phi3-mini-3.8b")


# --- admission control ------------------------------------------------------

@pytest.mark.tier1
def test_admission_queue_backpressure():
    sched = ContinuousScheduler(2, admission=AdmissionControl(max_pending=2))
    sched.submit(0, prompt_len=3, max_new=4, now=0.0)
    sched.submit(1, prompt_len=3, max_new=4, now=0.0)
    with pytest.raises(AdmissionError, match="queue full"):
        sched.submit(2, prompt_len=3, max_new=4, now=0.0)
    assert sched.n_rejected == 1
    # draining the queue re-opens admission
    sched.refill(now=0.0)
    sched.submit(2, prompt_len=3, max_new=4, now=1.0)


@pytest.mark.tier1
def test_admission_rejects_unservable_shapes():
    ac = AdmissionControl(max_pending=8, max_total_len=8)
    sched = ContinuousScheduler(2, admission=ac)
    with pytest.raises(AdmissionError, match="cache rows"):
        sched.submit(0, prompt_len=6, max_new=4, now=0.0)
    with pytest.raises(AdmissionError, match="empty request"):
        sched.submit(1, prompt_len=0, max_new=4, now=0.0)
    assert sched.n_rejected == 2
    assert not sched.requests                # rejected requests leave no state


# --- continuous vs static refill -------------------------------------------

@pytest.mark.tier1
def test_continuous_refill_reuses_freed_slot_immediately():
    sched = ContinuousScheduler(2, mode="continuous")
    for rid in range(3):
        sched.submit(rid, prompt_len=1, max_new=2, now=0.0)
    placed = sched.refill(now=0.0)
    assert [r.rid for _, r in placed] == [0, 1]      # FIFO admission
    sched.advance_prefill(0, 1, now=1.0)
    sched.record_token(0, now=1.0)
    assert sched.record_token(0, now=2.0)            # rid 0 finished
    placed = sched.refill(now=2.0)
    assert [(i, r.rid) for i, r in placed] == [(0, 2)]
    assert sched.requests[1].phase != "done"         # rid 1 still mid-flight


@pytest.mark.tier1
def test_static_refill_waits_for_the_whole_wave():
    sched = ContinuousScheduler(2, mode="static")
    for rid in range(3):
        sched.submit(rid, prompt_len=1, max_new=1, now=0.0)
    assert len(sched.refill(now=0.0)) == 2
    sched.advance_prefill(0, 1, now=1.0)
    assert sched.record_token(0, now=1.0)            # slot 0 drained ...
    assert sched.refill(now=1.0) == []               # ... but the wave holds
    sched.advance_prefill(1, 1, now=2.0)
    assert sched.record_token(1, now=2.0)
    assert [r.rid for _, r in sched.refill(now=2.0)] == [2]


@pytest.mark.tier1
def test_request_lifecycle_phases_and_timestamps():
    sched = ContinuousScheduler(1)
    req = sched.submit(0, prompt_len=2, max_new=2, now=5.0)
    assert req.phase == "queued"
    sched.refill(now=6.0)
    assert req.phase == "prefill" and req.admit_time == 6.0
    sched.advance_prefill(0, 2, now=7.0)
    assert req.phase == "decode" and req.prefill_end == 7.0
    sched.record_token(0, now=8.0)
    assert req.first_token == 8.0
    sched.record_token(0, now=9.0)
    assert req.phase == "done" and req.finish == 9.0
    assert 5.0 <= req.admit_time <= req.prefill_end <= req.first_token \
        <= req.finish


# --- step-cost model --------------------------------------------------------

def test_step_cost_model_from_default_point():
    cost = StepCostModel.from_operating_point(None)
    assert cost.source == "default"
    assert 0 < cost.cycles_prefill_token < cost.cycles_decode_token
    c1, e1 = cost.step_cost(1)
    c8, e8 = cost.step_cost(8)
    assert c8 > c1 and e8 > e1               # padded width is paid for
    cp, ep = cost.step_cost(8, prefill_tokens=4)
    assert cp > c8 and ep > e8               # chunked prefill costs extra


# --- straggler-aware dispatch ----------------------------------------------

def _drive(dispatch, steps=64):
    total = 0.0
    now = 0.0
    for _ in range(steps):
        dt = dispatch.step(100.0, now)
        total += dt
        now += dt
    return total


@pytest.mark.tier1
def test_host_dispatch_flags_only_the_slow_host():
    disp = HostDispatch(4, min_samples=8)
    disp.set_speed(2, 3.0)
    adaptive_cycles = _drive(disp)
    assert disp.flagged_hosts == [2]
    assert disp.weights[2] < 1.0             # work shifted off the straggler
    assert disp.weights[0] == disp.weights[1] == disp.weights[3] == 1.0
    assert disp.dead(64 * 400.0) == []       # slow-but-beating is not dead

    rigid = HostDispatch(4, min_samples=8, threshold=float("inf"))
    rigid.set_speed(2, 3.0)
    assert _drive(rigid) / adaptive_cycles > 1.5


@pytest.mark.tier1
def test_host_dispatch_healthy_cluster_stays_unflagged():
    disp = HostDispatch(4, min_samples=8)
    _drive(disp)
    assert disp.flagged_hosts == []
    assert disp.weights == [1.0] * 4


# --- virtual-time simulation ------------------------------------------------

def _mini_trace():
    """Two bursts of 4 on 2 slots: short and long requests mixed so wave
    batching leaves slots idle behind the longest request."""
    out = []
    for b in range(2):
        for i in range(4):
            rid = 4 * b + i
            out.append(TraceRequest(rid, arrival=b * 2000.0 + i * 5.0,
                                    prompt_len=2 + (i % 2) * 2,
                                    max_new=2 if i % 2 else 10))
    return out


@pytest.mark.tier1
def test_simulate_serve_is_deterministic_and_complete():
    slo = ServeSLO(p99_cycles_per_token=1e6)
    a = simulate_serve(_mini_trace(), 2, FLAT, mode="continuous", slo=slo)
    b = simulate_serve(_mini_trace(), 2, FLAT, mode="continuous", slo=slo)
    assert a.to_dict() == b.to_dict()
    assert a.n_completed == 8 and a.n_unfinished == 0 and a.n_rejected == 0
    assert a.tokens_out == sum(r.max_new for r in _mini_trace())
    assert a.p50_latency <= a.p99_latency
    assert 0.0 <= a.slo["attainment"] <= 1.0
    assert a.slo["throughput_at_slo"] <= a.throughput + 1e-12


@pytest.mark.tier1
def test_continuous_beats_static_on_bursty_mix():
    slo = ServeSLO(p99_cycles_per_token=1e6)
    cont = simulate_serve(_mini_trace(), 2, FLAT, mode="continuous", slo=slo)
    stat = simulate_serve(_mini_trace(), 2, FLAT, mode="static", slo=slo)
    # freed slots refill behind the long requests: strictly fewer steps, so
    # less total time, less padded-slot energy, and lower p99
    assert cont.total_cycles < stat.total_cycles
    assert cont.energy_per_token < stat.energy_per_token
    assert cont.p99_latency < stat.p99_latency


def test_simulate_serve_sheds_load_beyond_max_pending():
    trace = [TraceRequest(i, arrival=0.0, prompt_len=1, max_new=4)
             for i in range(8)]
    rep = simulate_serve(trace, 2, FLAT, mode="continuous",
                         slo=ServeSLO(p99_cycles_per_token=1e6),
                         admission=AdmissionControl(max_pending=3))
    # 2 go straight to slots on the first refill sweep is NOT how admission
    # works: all 8 arrive at t=0, the queue holds 3, the rest are shed
    assert rep.n_rejected == 5
    assert rep.n_completed == 3
    assert rep.n_unfinished == 0


# --- live engine ------------------------------------------------------------

def test_engine_midrun_admission_matches_fresh_engine():
    """The continuous-batching core: a request admitted into a freed slot
    mid-run decodes exactly the tokens it would on a fresh engine."""
    cfg = _cfg()
    params = init_model_params(KEY, cfg)
    prompt, max_new = [7, 3, 9, 1], 5

    eng = ServeEngine(params, cfg, RC, batch_slots=2, max_len=64)
    eng.submit([1, 2, 3], max_new=8)
    eng.submit([4, 5, 6], max_new=2)         # finishes early, frees its slot
    for _ in range(4):
        eng.step()
    rid = eng.submit(prompt, max_new=max_new)
    done = eng.run()
    assert len(done) == 3

    fresh = ServeEngine(params, cfg, RC, batch_slots=2, max_len=64)
    rid_f = fresh.submit(prompt, max_new=max_new)
    assert done[rid].generated == fresh.run()[rid_f].generated


def test_engine_admission_error_and_metrics():
    cfg = _cfg()
    params = init_model_params(KEY, cfg)
    eng = ServeEngine(params, cfg, RC, batch_slots=2, max_len=16)
    with pytest.raises(AdmissionError, match="cache rows"):
        eng.submit(list(range(14)), max_new=8)
    t_before = time.perf_counter()
    eng.submit([1, 2, 3], max_new=4)
    eng.run()
    t_after = time.perf_counter()
    rep = eng.metrics(slo=ServeSLO(p99_cycles_per_token=1e9))
    assert rep.mode == "continuous"
    assert rep.n_completed == 1 and rep.n_rejected == 1
    assert rep.tokens_out == 4
    assert rep.slo["attainment"] == 1.0
    assert rep.cost_source == "host_clock" and rep.total_energy == 0.0
    # TTFTs are host seconds inside the test's own clock readings
    assert 0.0 < rep.p50_ttft <= rep.p99_ttft <= t_after - t_before


def test_engine_static_mode_still_serves_everything():
    cfg = _cfg()
    params = init_model_params(KEY, cfg)
    eng = ServeEngine(params, cfg, RC, batch_slots=2, max_len=64,
                      mode="static")
    rids = [eng.submit([1 + i, 2, 3], max_new=3) for i in range(3)]
    done = eng.run()
    assert set(done) == set(rids)
    assert all(len(r.generated) == 3 for r in done.values())


@pytest.mark.tier1
def test_engine_refuses_empty_prompt_before_any_state():
    """Regression: an empty prompt must be shed at admission, before any
    engine-side Request state exists — never reach the batch-assembly path
    (which indexes ``prompt[-1]``)."""
    cfg = _cfg()
    eng = ServeEngine({}, cfg, RC, batch_slots=2, max_len=16)
    with pytest.raises(AdmissionError, match="empty request"):
        eng.submit([], max_new=4)
    with pytest.raises(AdmissionError, match="empty request"):
        eng.submit([1, 2], max_new=0)
    assert not eng.requests and not eng.sched.requests
    assert eng.sched.n_rejected == 2


# --- live-engine chunked prefill -------------------------------------------

def _slot_rows(cache, i):
    """Slot ``i``'s rows of every cache leaf (batch is axis 0 of ``len``,
    axis 1 of stacked leaves)."""
    return {k: (v if v.ndim == 0 else v[i] if v.ndim == 1 else v[:, i])
            for k, v in cache.items()}


def test_engine_chunked_prefill_mixed_phase_bit_exact():
    """One slot mid-prefill-chunk while its neighbour decodes: the chunked
    engine's generated tokens equal the token-by-token reference's, and
    each request's cache rows *at its completion step* match it: rows the
    request wrote within float rounding (a chunk is one parallel pass, not
    C decode steps), the rows past its length and its length exactly.
    (Rows are snapshotted at completion: once a slot frees, later steps may
    overwrite it with junk that the next refill zeroes — comparing
    end-of-run rows of freed slots would compare that junk.)  The name is
    older than the parallel chunk: the bit-exact part is now the rows the
    request did not write."""
    cfg = _cfg()
    params = init_model_params(KEY, cfg)
    # rid 0: short prompt, decodes while rid 1 is still chunk-prefilling
    reqs = [([5, 9], 8), (list(range(1, 19)), 3)]

    def run_with_snapshots(prefill):
        eng = ServeEngine(params, cfg, RC, batch_slots=2, max_len=64,
                          prefill=prefill, prefill_chunk=4)
        rids = [eng.submit(p, max_new=m) for p, m in reqs]
        snaps, slot_of = {}, {}
        for _ in range(200):
            if not eng.sched.busy:
                break
            for i, s in enumerate(eng.sched.slots):
                if s is not None:
                    slot_of[s.rid] = i
            eng.step()
            for rid in eng.finished:
                if rid not in snaps:
                    snaps[rid] = _slot_rows(eng.cache, slot_of[rid])
        assert set(eng.finished) == set(rids)
        return eng, snaps

    chunked, snaps_c = run_with_snapshots("chunked")
    token, snaps_t = run_with_snapshots("token")
    for rid in chunked.finished:
        assert chunked.finished[rid].generated == \
            token.finished[rid].generated
        rows_c, rows_t = snaps_c[rid], snaps_t[rid]
        assert set(rows_c) == set(rows_t)
        n = int(rows_c["len"])
        assert n == int(rows_t["len"])
        for k in set(rows_c) - {"len"}:
            # (L, Hkv, T, hd): positions below the length were written
            c, t = np.asarray(rows_c[k]), np.asarray(rows_t[k])
            np.testing.assert_allclose(c[:, :, :n], t[:, :, :n], rtol=1e-5,
                                       atol=1e-5, err_msg=f"rid {rid} {k!r}")
            assert np.array_equal(c[:, :, n:], t[:, :, n:]), \
                f"rid {rid} cache leaf {k!r} past its length"
    # the chunked run actually took fewer engine steps (that is the point)
    assert chunked._n_steps < token._n_steps


def test_engine_readmission_during_neighbour_prefill():
    """A request admitted into a freed slot while its neighbour is still
    mid-prefill decodes exactly the tokens it would on a fresh engine."""
    cfg = _cfg()
    params = init_model_params(KEY, cfg)
    prompt, max_new = [7, 3, 9, 1], 5

    eng = ServeEngine(params, cfg, RC, batch_slots=2, max_len=64,
                      prefill_chunk=4)
    eng.submit([4, 5, 6], max_new=2)          # finishes early, frees slot 0
    eng.submit(list(range(1, 25)), max_new=4)  # long prefill in slot 1
    for _ in range(3):
        eng.step()
    rid = eng.submit(prompt, max_new=max_new)
    # the readmission lands while slot 1 is still prefilling
    assert any(s is not None and s.phase == "prefill"
               for s in eng.sched.slots)
    done = eng.run()
    assert len(done) == 3

    fresh = ServeEngine(params, cfg, RC, batch_slots=2, max_len=64,
                        prefill_chunk=4)
    rid_f = fresh.submit(prompt, max_new=max_new)
    assert done[rid].generated == fresh.run()[rid_f].generated


def test_engine_chunk_bucket_jit_cache_is_bounded():
    """Varied prompt lengths across many requests hit at most
    log2(prefill_chunk) + 1 chunk buckets — the jit cache never grows past
    that, however long the engine runs."""
    cfg = _cfg()
    params = init_model_params(KEY, cfg)
    eng = ServeEngine(params, cfg, RC, batch_slots=2, max_len=64,
                      prefill_chunk=8)
    for plen in (1, 2, 3, 5, 8, 13, 21, 6, 17):
        eng.submit(list(range(1, plen + 1)), max_new=2)
    eng.run(max_steps=4000)
    assert not eng.sched.busy
    max_compiles = 4                      # log2(8) + 1: widths 1, 2, 4, 8
    assert 1 <= eng.prefill_compiles <= max_compiles
    assert set(eng._prefill_jit) <= {1, 2, 4, 8}


# --- measured-traffic operating points --------------------------------------

@pytest.mark.tier1
def test_traffic_estimator_levels():
    est = TrafficEstimator(capacity_tokens_per_cycle=0.01, min_arrivals=4)
    assert est.level() is None            # cold: no evidence, no level
    # a thundering herd (zero gaps) saturates offered load -> "high"
    for i in range(6):
        est.observe(now=0.0, prompt_len=8, max_new=8)
    assert est.offered_load() == 1.0 and est.level() == "high"
    # sparse arrivals (gap >> work/capacity) decay the estimate -> "low"
    est2 = TrafficEstimator(capacity_tokens_per_cycle=0.01, min_arrivals=4)
    for i in range(8):
        est2.observe(now=i * 1e6, prompt_len=8, max_new=8)
    assert est2.offered_load() < 0.3 and est2.level() == "low"


@pytest.mark.tier1
def test_scheduler_estimator_observes_shed_arrivals_too():
    est = TrafficEstimator(capacity_tokens_per_cycle=0.01, min_arrivals=1)
    sched = ContinuousScheduler(1, admission=AdmissionControl(max_pending=1),
                                estimator=est)
    sched.submit(0, prompt_len=2, max_new=4, now=0.0)
    with pytest.raises(AdmissionError):
        sched.submit(1, prompt_len=2, max_new=4, now=1.0)
    assert est.n_arrivals == 2            # rejected arrivals are load too


def test_engine_measured_traffic_retargets_at_refill():
    """The engine no longer measures traffic, so no refill retargets it: a
    pinned ``traffic`` level resolves the operating point at startup,
    attaches no traffic estimator and holds across every refill; the
    operating point never changes the generated tokens."""
    cfg = _cfg()
    params = init_model_params(KEY, cfg)
    eng = ServeEngine(params, cfg, RC, batch_slots=2, max_len=64)
    rids = [eng.submit([1 + i, 2, 3], max_new=2) for i in range(5)]
    done = eng.run()
    assert set(done) == set(rids)

    pinned = ServeEngine(params, cfg, RC, batch_slots=2, max_len=64,
                         traffic="medium")
    assert pinned.sched.estimator is None and pinned.traffic == "medium"
    op = pinned.operating_point
    rids_p = [pinned.submit([1 + i, 2, 3], max_new=2) for i in range(5)]
    done_p = pinned.run()
    assert pinned.operating_point is op and pinned.traffic == "medium"
    for a, b in zip(rids, rids_p):
        assert done[a].generated == done_p[b].generated


def test_engine_warmup_compiles_every_program_up_front():
    """After warmup no served step compiles, and the warm-up calls leave the
    cache as it was: the tokens match an engine that never warmed up."""
    cfg = _cfg()
    params = init_model_params(KEY, cfg)
    prompts = [list(range(1, 12)), [5, 9], [3, 1, 4, 1, 5, 9]]

    def serve(warm):
        eng = ServeEngine(params, cfg, RC, batch_slots=2, max_len=64,
                          prefill_chunk=4)
        seconds = eng.warmup() if warm else {}
        compiles = eng.prefill_compiles
        rids = [eng.submit(p, max_new=4) for p in prompts]
        done = eng.run()
        return seconds, eng.prefill_compiles - compiles, \
            [done[r].generated for r in rids]

    seconds, new_compiles, tokens = serve(warm=True)
    assert set(seconds) == {"decode_step", "prefill_step[1]",
                            "prefill_step[2]", "prefill_step[4]"}
    assert new_compiles == 0
    assert tokens == serve(warm=False)[2]


# --- chip_smoke.py's logits check ---------------------------------------------

def _chip_smoke():
    import importlib.util
    from pathlib import Path
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("decode_offset", [0, 1])
def test_chip_smoke_logits_check(decode_offset):
    """The check chip_smoke.py runs on the chip, at the reduced preset: the
    bf16 engine's teacher-forced logits agree with the float32 forward
    within its tolerance, and a decode cache position off by one fails it
    while the prefill positions still pass."""
    cs = _chip_smoke()
    from repro.launch.serve import build_engine
    cfg = _cfg()
    eng = build_engine(cfg, 0, batch_slots=4, max_len=64, prefill_chunk=8)
    prompt = [int(t) for t in jax.random.randint(KEY, (20,), 0, cfg.vocab)]
    rid = eng.submit(prompt, max_new=6)
    generated = eng.run()[rid].generated
    if decode_offset:
        decode = eng.decode_fn
        eng.decode_fn = lambda p, c, b: decode(
            p, {**c, "len": c["len"] + decode_offset}, b)
    err = cs.logits_error(eng, prompt, generated)
    assert err.shape == (len(prompt) + len(generated) - 1,)
    assert err[:len(prompt)].max() <= cs.LOGITS_TOL
    assert (err.max() <= cs.LOGITS_TOL) == (decode_offset == 0)


# --- host clock, step log, program names --------------------------------------

def test_engine_step_log_one_record_per_step(monkeypatch):
    """Every ``step()`` call, empty ones included, appends one record with
    ordered stamps, the program it ran and the prompt tokens it took; the
    process-wide log is bounded."""
    from collections import deque
    from repro.serve import telemetry
    assert telemetry.LOG.maxlen == telemetry.MAXLEN == 65536
    monkeypatch.setattr(telemetry, "LOG", deque(maxlen=5))
    cfg = _cfg()
    eng = ServeEngine(init_model_params(KEY, cfg), cfg, RC, batch_slots=2,
                      max_len=64, prefill_chunk=4)
    eng.submit(list(range(1, 7)), max_new=2)      # 6 prompt tokens: 4 + 2
    kinds, prompt_tokens = [], []
    for _ in range(4):
        eng.step()
        rec = telemetry.LOG[-1]
        kinds.append(rec.kind)
        prompt_tokens.append(rec.prompt_tokens)
        assert rec.t0 <= rec.refill <= rec.assemble <= rec.dispatch \
            <= rec.wait <= rec.fetch <= rec.t1
        assert sum(telemetry.phase_seconds(rec).values()) == \
            pytest.approx(rec.t1 - rec.t0)
    assert kinds == ["prefill", "prefill", "decode", "empty"]
    assert prompt_tokens == [4, 2, 0, 0]
    assert [r.width for r in telemetry.LOG] == [4, 2, 1, 0]
    assert [r.live for r in telemetry.LOG] == [1, 1, 1, 0]
    assert [r.placed for r in telemetry.LOG] == [1, 0, 0, 0]
    for _ in range(3):
        eng.step()
    assert len(telemetry.LOG) == 5                # bounded at maxlen
    assert all(r.kind == "empty" for r in list(telemetry.LOG)[-3:])
    t = telemetry.LOG[-1].t1
    assert telemetry.steps(t_from=t) == []
    assert telemetry.steps(t_to=t) == list(telemetry.LOG)


def test_step_log_summary_reads_every_counter():
    """The operator's one-line summary counts each kind of step and reads
    the live slots, refills, chunk width and prompt tokens of the log."""
    from repro.serve import telemetry

    def rec(t0, kind, width, prompt_tokens, live, placed):
        return telemetry.StepRecord(*(t0 + 1e-3 * k for k in range(7)),
                                    kind, width, prompt_tokens, live, placed)
    line = telemetry.summary([rec(0.0, "prefill", 8, 12, 3, 2),
                              rec(1.0, "prefill", 4, 4, 4, 1),
                              rec(2.0, "decode", 1, 0, 4, 0),
                              rec(3.0, "empty", 0, 0, 0, 0)])
    assert line.startswith("4 steps (2 prefill, 1 decode, 1 empty); "
                           "3.67 live slots per busy step, 3 refills; "
                           "prefill step width 6.0, 8.0 prompt tokens; ")
    assert "serve.wait 1.000" in line and "serve.commit 1.000" in line
    assert telemetry.summary([]).startswith("0 steps (0 prefill")


def test_engine_lifecycle_stamps_on_the_host_clock():
    """Every lifecycle stamp the engine writes is ``perf_counter`` seconds
    inside the caller's own readings, in lifecycle order."""
    cfg = _cfg()
    eng = ServeEngine(init_model_params(KEY, cfg), cfg, RC, batch_slots=2,
                      max_len=64)
    t_before = time.perf_counter()
    rids = [eng.submit([1 + i, 2, 3, 4], max_new=3) for i in range(3)]
    eng.run()
    t_after = time.perf_counter()
    for rid in rids:
        r = eng.sched.requests[rid]
        assert t_before <= r.arrival <= r.admit_time <= r.prefill_end \
            <= r.first_token <= r.finish <= t_after
    # the third request waited for a slot: admitted after the first step
    third = eng.sched.requests[rids[2]]
    assert third.admit_time > eng.sched.requests[rids[0]].first_token


def test_engine_step_programs_are_named():
    """The jitted step programs lower as ``jit_decode_step`` and
    ``jit_prefill_step``, so a device trace names them."""
    cfg = _cfg()
    eng = ServeEngine(init_model_params(KEY, cfg), cfg, RC, batch_slots=2,
                      max_len=32)
    n = eng.sched.n_slots
    dec = eng.decode_fn.lower(eng.params, eng.cache,
                              {"tokens": jnp.zeros((n, 1), jnp.int32)})
    pre = eng.prefill_fn(2).lower(
        eng.params, eng.cache, {"tokens": jnp.zeros((n, 2), jnp.int32),
                                "n_tokens": jnp.zeros((n,), jnp.int32)})
    assert "module @jit_decode_step " in dec.as_text()
    assert "module @jit_prefill_step " in pre.as_text()
