"""The program's names in a profile: step phases, host spans, the counter
and the answer tail, on a small recorded TPU trace (``trace_v5e.json``,
written by ``record_trace.py``) and on hand-made events."""
import json
import pathlib

import pytest

import benchtiny
from simbench import answers, metrics, phases, trace

HERE = pathlib.Path(__file__).resolve().parent
FIXTURE = HERE / "trace_v5e.json"
DEV = "/device:TPU:0"
HOST = "/host:CPU"
NEW = ("inject_ms_per_slot", "vc_prearb_ms_per_slot", "route_ms_per_slot",
       "out_arb_ms_per_slot", "moves_ms_per_slot", "link_ms_per_slot",
       "program_ms_per_slot", "stepped_per_counted",
       "admission_ms_per_answer", "prepare_ms_per_answer", "answer_tail_ms")


def recorded() -> list:
    fx = json.loads(FIXTURE.read_text())
    return [phases.Event(fx["planes"][p], fx["lines"][ln], fx["names"][n],
                         float(s), float(d), fx["paths"][q], st or None)
            for p, ln, n, s, d, q, st in fx["events"]]


@pytest.fixture(scope="module")
def events():
    return recorded()


@pytest.fixture(scope="module")
def stretch(events):
    return trace.span(events, phases.STRETCH)


def E(line, name, start, dur, path="", stats=None, plane=DEV):
    return phases.Event(plane, line, name, start, dur, path, stats)


def test_fixture_is_a_small_tpu_trace():
    fx = json.loads(FIXTURE.read_text())
    assert FIXTURE.stat().st_size <= 200 * 1024
    assert fx["device"].startswith("TPU")
    assert {trace.OPS_LINE, trace.MODULES_LINE} <= set(fx["lines"])


def test_every_phase_is_read_and_covers_the_loop(events, stretch):
    split = phases.phase_s(events, *stretch)
    assert all(split[p] > 0 for p in phases.PHASES)
    named = sum(split[p] for p in phases.PHASES)
    assert 0.9 * split["loop"] <= named <= split["loop"] <= split["all"]
    whole = trace.reduce(events, *stretch, phases.LOOP_EXECUTABLES,
                         phases.STRETCH)
    assert split["loop"] <= whole["loop_s"]
    # self times partition the busy time: nothing counts twice
    assert split["all"] == pytest.approx(whole["busy_s"])


def test_program_scope_only_in_the_program_loop(events):
    paths = {ev.path for ev in events if "program" in ev.path.split("/")}
    assert paths and all(p.startswith("jit(_program_loop)") for p in paths)


def test_a_container_counts_once():
    loop = "jit(_program_loop)/while/body/closed_call"
    ops = [E(trace.OPS_LINE, "while.1", 100, 100, "jit(_program_loop)/while"),
           E(trace.OPS_LINE, "fusion.1", 105, 40, f"{loop}/route/gather"),
           E(trace.OPS_LINE, "custom-call.1", 105, 0),   # folded, 0 ns
           E(trace.OPS_LINE, "fusion.2", 150, 50, f"{loop}/link/select"),
           E(trace.OPS_LINE, "copy.1", 300, 10, "jit(f)/copy")]
    own = {ev.name: t for ev, t in phases.self_times(ops, 0, 1000)}
    assert own == {"while.1": 10e-9, "fusion.1": 40e-9, "fusion.2": 50e-9,
                   "copy.1": 10e-9}
    split = phases.phase_s(ops, 0, 1000)
    assert split["route"] == 40e-9 and split["link"] == 50e-9
    assert split["loop"] == pytest.approx(100e-9)
    assert split["all"] == pytest.approx(110e-9)
    assert phases.phase_s(ops, 150, 1000)["loop"] == pytest.approx(50e-9)
    assert phases.phase_s(ops[4:], 0, 1000) is None   # no phase names


def test_answer_tails(events, stretch):
    runs = [ev for ev in events if ev.name == phases.ANSWER]
    tails = phases.tails(events, *stretch)
    assert len(runs) == len(tails) == 2
    for run, tail in zip(runs, tails):
        assert 0 < tail < run.dur_ns
    hand = [E(trace.MODULES_LINE, "jit__program_loop(1)", 10, 50),
            E(trace.MODULES_LINE, "jit__program_loop(1)", 70, 20),
            E(trace.MODULES_LINE, "jit_copy(2)", 92, 3),
            E("python", "api.run", 0, 100, plane=HOST)]
    assert phases.tails(hand, 0, 200) == [10]


def test_host_spans_and_counter(events, stretch):
    for name in ("api.admission", "runner.prepare"):
        ms = phases.span_ms_per_answer(events, *stretch, name)
        spans = [ev.dur_ns for ev in events if ev.name == name]
        assert ms == pytest.approx(sum(spans) / 2 / 1e6) and ms > 0
    # the window All2All steps whole chunks of 4, the 2-replica uniform
    # batch 2 * (2 + 2) slots
    stepped = phases.counted(events, *stretch, phases.STEPPED)
    assert stepped > 8 and (stepped - 8) % 4 == 0
    assert phases.counted(events, *stretch, "no such counter") is None


def _space(events) -> bytes:
    """An ``.xplane.pb`` holding ``events``, in the schema ``phases``
    reads."""
    space = phases._space_class()()
    for name in dict.fromkeys(ev.plane for ev in events):
        plane = space.planes.add(name=name)
        stat_ids, meta_ids = {}, {}

        def stat_id(key):
            if key not in stat_ids:
                stat_ids[key] = len(stat_ids) + 1
                plane.stat_metadata.add(key=stat_ids[key]).value.name = key
            return stat_ids[key]

        def meta_id(ev):
            key = (ev.name, ev.path)
            if key not in meta_ids:
                meta_ids[key] = len(meta_ids) + 1
                md = plane.event_metadata.add(key=meta_ids[key]).value
                md.name = ev.name
                if ev.path:
                    md.stats.add(metadata_id=stat_id(phases.OP_PATH),
                                 str_value=ev.path)
            return meta_ids[key]

        mine = [ev for ev in events if ev.plane == name]
        for line_name in dict.fromkeys(ev.line for ev in mine):
            line = plane.lines.add(name=line_name, timestamp_ns=0)
            for ev in mine:
                if ev.line != line_name:
                    continue
                x = line.events.add(metadata_id=meta_id(ev),
                                    offset_ps=int(ev.start_ns) * 1000,
                                    duration_ps=int(ev.dur_ns) * 1000)
                for k, v in (ev.stats or {}).items():
                    x.stats.add(metadata_id=stat_id(k), int64_value=int(v))
    return space.SerializeToString()


def _run_data(tmp_path, monkeypatch, events, slots: int):
    out = tmp_path / ".bench_trace" / "cell" / "plugins" / "t.xplane.pb"
    out.parent.mkdir(parents=True)
    out.write_bytes(_space(events))
    monkeypatch.chdir(tmp_path)
    assert phases.read(str(out)) == events
    lo, hi = trace.span(events, phases.STRETCH)
    reduced = trace.reduce(events, lo, hi, phases.LOOP_EXECUTABLES,
                           phases.STRETCH)
    return metrics.RunData({}, answers.Window(t0=0.0), 0, None, reduced,
                           slots)


def test_readers_on_the_recorded_trace(tmp_path, monkeypatch, events,
                                       stretch):
    stepped = phases.counted(events, *stretch, phases.STEPPED)
    run = _run_data(tmp_path, monkeypatch, events, stepped)
    got = {name: metrics.load_reader(name)(run) for name in NEW}
    split = phases.phase_s(events, *stretch)
    for p in phases.PHASES:
        assert got[f"{p}_ms_per_slot"] == pytest.approx(
            1e3 * split[p] / stepped)
    assert got["stepped_per_counted"] == 1.0
    tails = phases.tails(events, *stretch)
    assert got["answer_tail_ms"] == pytest.approx(sum(tails) / 2 / 1e6)
    assert got["admission_ms_per_answer"] > 0
    assert got["prepare_ms_per_answer"] > 0


def test_readers_find_nothing_in_a_program_without_names(
        tmp_path, monkeypatch, events):
    # the parent commit's program: no scopes in op paths, no spans, no
    # counter -- every new reader reads None and none raises
    bare = [ev._replace(path="") for ev in events
            if ev.name not in (phases.ANSWER, phases.STEPPED,
                               "api.admission", "runner.prepare")]
    run = _run_data(tmp_path, monkeypatch, bare, 10)
    assert {name: metrics.load_reader(name)(run) for name in NEW} == \
        dict.fromkeys(NEW)


def test_readers_take_only_this_runs_trace(tmp_path, monkeypatch, events):
    run = _run_data(tmp_path, monkeypatch, events, 10)
    run.trace = dict(run.trace, window_s=run.trace["window_s"] + 1.0)
    assert phases.of_run(run) is None
    run.trace = None
    assert all(metrics.load_reader(name)(run) is None for name in NEW)


def test_a_traced_cpu_run_reports_no_new_metric(tmp_path, monkeypatch):
    # on the CPU the trace has no device planes: the new readers find
    # nothing and the run still ends with its result line
    root = benchtiny.tiny_root(tmp_path)
    monkeypatch.chdir(root)
    rc, line = benchtiny.run_cell(root, "a2a_w4.tiny_mrls", trace=1)
    assert rc == 0 and line["correct"]
    assert "table_build_s" in line["metrics"]
    assert not set(NEW) & set(line["metrics"])
