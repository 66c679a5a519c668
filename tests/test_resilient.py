"""Resilient execution runtime: deterministic backoff, dual-clock fault
counting, checkpoint round-trips of armed engine state, bounded-segment
parity, resumable drivers, the subprocess supervisor, and admission
control."""
import json
import os
import pathlib
import signal
import subprocess
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.api import (Experiment, NetworkSpec, RouteSpec, WorkloadSpec,
                       check_admission, estimate_memory, AdmissionError)
from repro.api.admission import (BASELINE_RSS_BYTES, DEFAULT_COMPILE_MULT,
                                 compile_ram_multiplier, predict_peak_rss)
from repro.api.registry import build_network
from repro.checkpointing.checkpoint import Checkpointer
from repro.core.failures import FailureSchedule
from repro.core.routing import build_tables
from repro.runtime.fault_tolerance import (BackoffPolicy, FaultTolerantRunner,
                                           FTConfig)
from repro.runtime.resilient import (ResilientConfig,
                                     run_completion_resumable,
                                     run_program_resumable,
                                     run_window_resumable)
from repro.runtime.supervisor import (AdmissionRefused, Supervisor,
                                      SupervisorConfig)
from repro.simulator.engine import Simulator, Traffic
from repro.workloads import build_collective_program, compile_program

NET = NetworkSpec("mrls", {"n_leaves": 14, "u": 3, "d": 3, "seed": 0})
ROUTE = RouteSpec(policy="polarized", max_hops=10)


@pytest.fixture(scope="module")
def sim():
    topo = build_network(NET)
    s = Simulator(build_tables(topo), ROUTE.to_sim_config(seed=0))
    yield s


@pytest.fixture(scope="module")
def program(sim):
    return compile_program(
        build_collective_program("all2all", sim.S, rounds=2),
        schedule="window")


def _tree_equal(a, b):
    assert set(a) == set(b)
    for k in a:
        x, y = np.asarray(a[k]), np.asarray(b[k])
        assert x.dtype == y.dtype, k
        np.testing.assert_array_equal(x, y, err_msg=k)


# ---------------------------------------------------------------------- #
# backoff policy
# ---------------------------------------------------------------------- #
def test_backoff_deterministic_and_bounded():
    p = BackoffPolicy(base_s=0.5, factor=2.0, cap_s=30.0, jitter=0.1)
    assert p.delay(2, 5) == p.delay(2, 5)          # pure function
    # jitter decorrelates on the lifetime counter, not wall clock
    assert p.delay(2, 5) != p.delay(2, 6)
    for consecutive in (1, 2, 3, 7):
        d = p.delay(consecutive, 1)
        nominal = min(0.5 * 2.0 ** (consecutive - 1), 30.0)
        assert nominal * 0.9 <= d <= nominal * 1.1
    assert p.delay(40, 1) <= 30.0 * 1.1            # capped


def test_backoff_no_jitter_exact():
    p = BackoffPolicy(base_s=1.0, factor=2.0, cap_s=8.0, jitter=0.0)
    assert [p.delay(c, c) for c in (1, 2, 3, 4, 5)] == [1, 2, 4, 8, 8]


# ---------------------------------------------------------------------- #
# dual-clock fault counting
# ---------------------------------------------------------------------- #
def _counting_runner(tmp_path, fail_steps, cfg):
    ck = Checkpointer(str(tmp_path))
    fired = set()

    def hook(step):
        if step in fail_steps and step not in fired:
            fired.add(step)
            raise RuntimeError(f"injected @ {step}")

    slept = []
    r = FaultTolerantRunner(
        lambda s, b: (s + b["x"], {"loss": jnp.float32(1.0)}),
        lambda s: {"x": jnp.float32(s)}, ck, cfg, fault_hook=hook,
        sleep_fn=slept.append)
    return r, slept


def test_runner_scattered_transients_survive(tmp_path):
    # 3 one-off failures with successes in between: over max_consecutive=1
    # if counted on one clock, fine on two
    cfg = FTConfig(ckpt_every=2, max_retries=5, max_consecutive=1)
    r, slept = _counting_runner(tmp_path, {5, 9, 13}, cfg)
    state, step, _ = r.run(jnp.float32(0.0), 0, 16)
    assert step == 16 and float(state) == sum(range(16))
    assert r.total_failures == 3 and r.consecutive_failures == 0
    assert r.restarts == 3                         # back-compat alias
    # every retry was a first consecutive failure; jitter keyed on total
    expect = [cfg.backoff.delay(1, t) for t in (1, 2, 3)]
    assert r.delays == expect and slept == expect


def test_runner_hard_wedge_fails_fast(tmp_path):
    ck = Checkpointer(str(tmp_path))

    def hook(step):
        # wedge AT a checkpoint boundary: restore lands back on the same
        # step, so no intervening success resets the consecutive clock
        if step == 4:
            raise RuntimeError("wedged")           # every attempt

    r = FaultTolerantRunner(
        lambda s, b: (s + 1, {"loss": jnp.float32(1.0)}),
        lambda s: {"x": jnp.float32(s)}, ck,
        FTConfig(ckpt_every=2, max_retries=50, max_consecutive=2),
        fault_hook=hook, sleep_fn=lambda d: None)
    with pytest.raises(RuntimeError, match="wedged"):
        r.run(jnp.float32(0.0), 0, 10)
    assert r.consecutive_failures == 3             # limit + 1, then raise
    assert r.total_failures == 3 < 50


# ---------------------------------------------------------------------- #
# checkpoint round-trips of engine state
# ---------------------------------------------------------------------- #
def test_armed_state_checkpoint_roundtrip(tmp_path):
    # armed simulator: state carries the fused uint32 route rows (int16
    # distances in their top halves), the free-list ring, and live
    # link_up/fail_drop
    topo = build_network(NET)
    sched = FailureSchedule.random_links(topo, 2, down_slot=3, seed=0)
    s = Simulator(build_tables(topo), ROUTE.to_sim_config(seed=0),
                  failures=sched)
    tr = Traffic("all2all", rounds=2)
    st = s.run_chunk(s.make_state(tr, 0), tr, 8)   # past down_slot
    host = {k: np.asarray(v) for k, v in jax.device_get(st).items()}
    assert host["tbl_rows"].dtype == np.uint32
    assert host["tbl_rows"].shape == (s.n1 * s.N, s.K)
    assert host["link_up"].dtype == np.bool_

    ck = Checkpointer(str(tmp_path))
    ck.save(1, {"state": host})
    template = {"state": {k: np.zeros_like(v) for k, v in host.items()}}
    tree, meta = ck.restore(template, 1)
    _tree_equal(tree["state"], host)
    s.close()


def test_bfloat16_view_roundtrip(tmp_path):
    # npz cannot store bfloat16 natively; the checkpointer round-trips it
    # through a uint16 view — bits and dtype must both survive
    a = jnp.arange(7, dtype=jnp.bfloat16) * jnp.bfloat16(0.3)
    tree = {"a": a, "b": np.arange(5, dtype=np.uint32)}
    ck = Checkpointer(str(tmp_path))
    ck.save(1, tree)
    out, _ = ck.restore({"a": jnp.zeros(7, jnp.bfloat16),
                         "b": np.zeros(5, np.uint32)}, 1)
    assert np.asarray(out["a"]).dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(out["a"]).view(np.uint16),
        np.asarray(a).view(np.uint16))


# ---------------------------------------------------------------------- #
# bounded segments == unbounded loop, bitwise
# ---------------------------------------------------------------------- #
def test_program_bounded_equals_unbounded(sim, program):
    ref = sim.run_program(program, chunk=8, max_slots=2000, seed=0)
    st, running = None, True
    while running:
        r = sim.run_program(program, chunk=8, max_slots=2000, seed=0,
                            state=st, budget_chunks=2)
        st, running = r["state"], r["running"]
    assert r["slots"] == ref["slots"]
    assert r["completed"] == ref["completed"]
    assert r["pool_stall"] == ref["pool_stall"]
    assert tuple(r["phase_slots"]) == tuple(ref["phase_slots"])
    _tree_equal(jax.device_get(r["state"]), jax.device_get(ref["state"]))


def test_program_bounded_equals_unbounded_mid_chunk(sim, program):
    # completion inside a chunk: both loops stop at the completion slot
    ref = sim.run_program(program, chunk=4, max_slots=2000, seed=0)
    assert ref["completed"] and ref["slots"] % 4
    st, running, segments = None, True, 0
    while running:
        r = sim.run_program(program, chunk=4, max_slots=2000, seed=0,
                            state=st, budget_chunks=1)
        st, running = r["state"], r["running"]
        segments += 1
    assert segments == -(-ref["slots"] // 4) > 1
    assert r["slots"] == ref["slots"]
    assert r["completed"] == ref["completed"]
    assert r["pool_stall"] == ref["pool_stall"]
    assert tuple(r["phase_slots"]) == tuple(ref["phase_slots"])
    final = jax.device_get(r["state"])
    _tree_equal(final, jax.device_get(ref["state"]))
    assert int(final["slot"]) == ref["slots"]


@pytest.mark.parametrize("max_slots", [3, 6])
def test_window_timeout_stops_on_a_chunk_boundary(sim, program, max_slots):
    # max_slots is tested between chunk bodies only
    r = sim.run_program(program, chunk=4, max_slots=max_slots, seed=0)
    assert not r["completed"]
    rounded = 4 * -(-max_slots // 4)
    assert r["slots"] == rounded
    assert int(jax.device_get(r["state"]["slot"])) == rounded


def test_completion_bounded_equals_unbounded(sim):
    tr = Traffic("all2all", rounds=2)
    expected = sim.S * 2
    ref = sim.run_completion(tr, expected, chunk=8, max_slots=2000, seed=0)
    st, done, running = None, None, True
    while running:
        r = sim.run_completion(tr, expected, chunk=8, max_slots=2000,
                               seed=0, state=st, budget_chunks=2,
                               done=done)
        st, done, running = r["state"], r["done"], r["running"]
    assert r["slots"] == ref["slots"]
    assert r["completed"] == ref["completed"]
    assert r["pool_stall"] == ref["pool_stall"]


# ---------------------------------------------------------------------- #
# resumable drivers
# ---------------------------------------------------------------------- #
def test_program_resumable_matches_oneshot(sim, program, tmp_path):
    ref = sim.run_program(program, chunk=2, max_slots=2000, seed=0)
    r = run_program_resumable(sim, program, ckpt=str(tmp_path), chunk=2,
                              max_slots=2000, seed=0,
                              config=ResilientConfig(every=1))
    assert r["resumed_from"] is None and r["segments"] >= 2
    assert r["slots"] == ref["slots"]
    assert r["completed"] == ref["completed"]
    assert r["pool_stall"] == ref["pool_stall"]
    assert tuple(r["phase_slots"]) == tuple(ref["phase_slots"])


def test_program_resume_after_interrupt(sim, program, tmp_path):
    ref = sim.run_program(program, chunk=2, max_slots=2000, seed=0)
    full = run_program_resumable(sim, program, ckpt=str(tmp_path), chunk=2,
                                 max_slots=2000, seed=0,
                                 config=ResilientConfig(every=1, keep=100))
    assert full["segments"] >= 3
    # simulate a kill after segment 1: drop every later snapshot
    for d in pathlib.Path(tmp_path).iterdir():
        if d.name.startswith("step_") and int(d.name[5:]) > 1:
            for f in d.iterdir():
                f.unlink()
            d.rmdir()
    r = run_program_resumable(sim, program, ckpt=str(tmp_path), chunk=2,
                              max_slots=2000, seed=0,
                              config=ResilientConfig(every=1, keep=100))
    assert r["resumed_from"] == 1
    assert r["slots"] == ref["slots"]
    assert r["completed"] == ref["completed"]
    assert r["pool_stall"] == ref["pool_stall"]
    assert tuple(r["phase_slots"]) == tuple(ref["phase_slots"])


def test_resume_fingerprint_mismatch_raises(sim, program, tmp_path):
    run_program_resumable(sim, program, ckpt=str(tmp_path), chunk=8,
                          max_slots=2000, seed=0,
                          config=ResilientConfig(every=2))
    with pytest.raises(ValueError, match="different run configuration"):
        run_program_resumable(sim, program, ckpt=str(tmp_path), chunk=16,
                              max_slots=2000, seed=0,
                              config=ResilientConfig(every=2))


def test_window_resumable_matches_oneshot(sim, tmp_path):
    tr = Traffic("uniform", load=0.5)
    ref = sim.run_throughput(tr, warm=30, measure=50, seed=0)
    r = run_window_resumable(sim, tr, metric="throughput",
                             ckpt=str(tmp_path), warm=30, measure=50,
                             seed=0, config=ResilientConfig(every=7))
    assert r["resumed_from"] is None
    assert r["throughput"] == ref["throughput"]
    assert r["avg_hops"] == ref["avg_hops"]
    assert r["ejected"] == ref["ejected"]
    assert r["pool_stall"] == ref["pool_stall"]


def test_completion_resumable_matches_oneshot(sim, tmp_path):
    tr = Traffic("all2all", rounds=2)
    expected = sim.S * 2
    ref = sim.run_completion(tr, expected, chunk=8, max_slots=2000, seed=0)
    r = run_completion_resumable(sim, tr, expected, ckpt=str(tmp_path),
                                 chunk=8, max_slots=2000, seed=0,
                                 config=ResilientConfig(every=2))
    assert r["slots"] == ref["slots"]
    assert r["completed"] == ref["completed"]
    assert r["pool_stall"] == ref["pool_stall"]


# ---------------------------------------------------------------------- #
# supervisor
# ---------------------------------------------------------------------- #
_PY = sys.executable


def _sup(**kw):
    kw.setdefault("poll_interval_s", 0.05)
    kw.setdefault("backoff", BackoffPolicy(base_s=0.0, jitter=0.0))
    return Supervisor(SupervisorConfig(**kw), sleep_fn=lambda d: None)


def test_supervisor_timeout_kill():
    res = _sup(timeout_s=0.3, max_retries=0).run(
        [_PY, "-c", "import time; time.sleep(30)"])
    assert not res.ok
    assert res.attempts[0].killed == "timeout"
    assert res.attempts[0].wall_s < 5


def test_supervisor_rss_kill():
    res = _sup(rss_budget_bytes=120 << 20, max_retries=0).run(
        [_PY, "-c",
         "b = bytearray(300 * 2**20); import time; time.sleep(30)"])
    assert not res.ok
    assert res.attempts[0].killed == "rss"
    assert res.peak_rss_bytes > 120 << 20


def test_supervisor_injected_kill_then_success():
    res = _sup(inject_kill_s=0.1, max_retries=2).run(
        [_PY, "-c", "import time; time.sleep(1.0)"])
    assert res.ok and res.retries == 1
    assert res.attempts[0].killed == "injected"
    assert res.attempts[1].ok


def test_supervisor_admission_preflight():
    sup = _sup(rss_budget_bytes=100)
    with pytest.raises(AdmissionRefused):
        sup.run([_PY, "-c", "pass"], predicted_bytes=200)


def test_supervisor_retries_exhaust_with_backoff():
    slept = []
    sup = Supervisor(
        SupervisorConfig(max_retries=2, poll_interval_s=0.05,
                         backoff=BackoffPolicy(base_s=0.25, jitter=0.0)),
        sleep_fn=slept.append)
    res = sup.run([_PY, "-c", "raise SystemExit(3)"])
    assert not res.ok and len(res.attempts) == 3
    assert all(a.returncode == 3 for a in res.attempts)
    assert slept == [0.25, 0.5]


# ---------------------------------------------------------------------- #
# admission control
# ---------------------------------------------------------------------- #
def _exp(**kw):
    return Experiment(network=NET, route=ROUTE,
                      workload=WorkloadSpec("uniform", load=0.5), **kw)


def test_admission_admits_within_budget():
    d = check_admission(_exp(), budget_bytes=1 << 40, records={})
    assert d.admitted and d.action == "admit"
    assert d.compile_mult == DEFAULT_COMPILE_MULT
    assert d.predicted_bytes == predict_peak_rss(d.resident_bytes,
                                                 d.compile_mult)


def test_admission_refuses_with_actionable_message():
    with pytest.raises(AdmissionError) as e:
        check_admission(_exp(), budget_bytes=1 << 20, records={})
    msg = str(e.value)
    assert "replicas" in msg and "blocked" in msg
    assert "REPRO_ADMISSION=warn" in msg


def test_admission_warn_mode_admits_over_budget():
    d = check_admission(_exp(), budget_bytes=2 << 20, mode="warn",
                        records={})
    assert d.admitted and d.reason


def test_admission_off_mode():
    d = check_admission(_exp(), mode="off")
    assert d.admitted and d.action == "off"


def test_admission_downgrades_to_blocked_masks():
    est = estimate_memory(_exp())
    assert est["tables"]["mask_layout"] == "dense"
    mult = 50_000.0     # synthetic at-scale record: big enough that the
    records = {"x": {"mrls": {"n_endpoints": 5000,      # masks matter
                              "compile_ram_multiplier": mult}}}
    hi = predict_peak_rss(est["total_bytes"], mult)
    lo = predict_peak_rss(
        est["total_bytes"] - est["tables"]["host_mask_bytes"], mult)
    assert lo < hi
    d = check_admission(_exp(), budget_bytes=(lo + hi) // 2,
                        records=records)
    assert d.admitted and d.action == "downgrade" and d.masks == "blocked"
    assert d.predicted_bytes <= (lo + hi) // 2
    assert d.compile_mult == mult


def test_compile_ram_multiplier_prefers_family_at_scale():
    records = {
        "s": {"mrls": {"n_endpoints": 50, "compile_ram_multiplier": 99.0},
              "fat_tree": {"n_endpoints": 9000,
                           "compile_ram_multiplier": 7.0},
              "dragonfly": {"n_endpoints": 2000,
                            "peak_rss_bytes": BASELINE_RSS_BYTES + 1000,
                            "est_total_bytes": 100}}}
    # sub-1000-endpoint record ignored even for the matching family
    assert compile_ram_multiplier("mrls", records) == 7.0   # largest
    assert compile_ram_multiplier("dragonfly", records) == 10.0
    assert compile_ram_multiplier("mrls", {}) == DEFAULT_COMPILE_MULT


# ---------------------------------------------------------------------- #
# end-to-end kill-resume (subprocess SIGKILL; the CI smoke runs the
# supervised variant — this one aims the kill at a live checkpoint chain)
# ---------------------------------------------------------------------- #
_CHILD_SRC = """
import sys
sys.path.insert(0, {src!r})
from repro.api import Experiment, run_resumable
exp = Experiment.from_json(open({spec!r}).read())
run_resumable(exp, {ckpt!r}, every=1)
"""


@pytest.mark.slow
def test_sigkill_resume_bitwise(tmp_path):
    from repro.api import run, resume
    root = pathlib.Path(__file__).resolve().parents[1]
    spec = root / "examples" / "specs" / "tiny_mrls_a2a.json"
    exp = Experiment.from_json(spec.read_text())
    ref = run(exp)

    ckpt = str(tmp_path / "ckpt")
    src = _CHILD_SRC.format(src=str(root / "src"), spec=str(spec),
                            ckpt=ckpt)
    proc = subprocess.Popen([_PY, "-c", src])
    # kill inside the run: after the child has written its spec, which a
    # loaded host can take well past a fixed sleep to reach
    started = pathlib.Path(ckpt) / "experiment.json"
    deadline = time.monotonic() + 300
    while (not started.exists() and proc.poll() is None
           and time.monotonic() < deadline):
        time.sleep(0.2)
    time.sleep(1.0)
    if proc.poll() is None:
        os.kill(proc.pid, signal.SIGKILL)
    proc.wait()

    got = resume(ckpt)                # finishes (or re-runs) the child
    assert json.loads(got.to_json()) == json.loads(ref.to_json())
