"""The split of a profiler trace by the program's named scopes and host
spans (``benchmarks/chip/scopes``), and the existing reduction of the
recorded trace pinned, so that reading event metadata changes nothing
``trace_reduce`` reports."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmarks.chip import scopes as sc         # noqa: E402
from benchmarks.chip import trace_reduce as tr   # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
RECORDED = DATA / "paper_csr10.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    return sc.read_planes(str(RECORDED))


def _ops(planes):
    dev = [p for p in planes if tr.OPS_LINE in p.lines]
    assert len(dev) == 1
    return {tr.short_name(e.name): e for e in dev[0].lines[tr.OPS_LINE]}


# -- the existing reduction of the recorded trace, pinned ------------------

def test_recorded_summary_is_unchanged():
    s = tr.reduce_file(str(RECORDED))
    assert s["n_devices"] == 1
    assert s["window_s"] == pytest.approx(0.030215357, rel=1e-9)
    assert s["busy_s"] == pytest.approx(0.021213479, rel=1e-9)
    assert s["idle_share"] == pytest.approx(0.2979239331840431, rel=1e-9)
    assert len(s["ops"]) == 116
    top = list(s["ops"].items())[:5]
    assert [k for k, _ in top] == ["fusion.143", "fusion.137",
                                   "multiply_subtract_fusion.2", "copy.60",
                                   "copy.58"]
    assert [v for _, v in top] == pytest.approx(
        [0.002740276, 0.00234643, 0.002173304, 0.002101152, 0.002083037],
        rel=1e-6)
    assert s["op_counts"]["fusion.143"] == 87
    assert len(s["gaps"]) == 42
    assert [k for k, _ in s["gaps"][:5]] == [
        "bench.traced/PjitFunction(dynamic_slice)",
        "bench.eval/$array.py:631 _value",
        "bench.traced/PjitFunction(reshape)",
        "bench.eval/PjitFunction(evaluate)",
        "bench.traced/$slicing.py:1485 _slice_impl"]
    assert s["gaps"][1][1] == pytest.approx(0.002314994, rel=1e-6)
    b = tr.breakdown(s)
    assert b["device_ops"][0] == ["fusion.143", s["ops"]["fusion.143"]]
    assert len(b["idle_gaps"]) == 10


# -- the event-metadata reader ---------------------------------------------

def test_reader_gives_the_events_profile_data_gives(recorded):
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(RECORDED))
    compared = 0
    for plane, mine in zip(pd.planes, recorded):
        assert plane.name == mine.name
        for line in plane.lines:
            if line.name in mine.lines:
                want = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
                got = [(e.name, e.start_ns, e.end_ns)
                       for e in mine.lines[line.name]]
                assert got == want, (plane.name, line.name)
                compared += len(got)
    assert compared > 10_000


def test_reader_finds_each_ops_name_stack_and_category(recorded):
    # tf_op is "<name stack>:<op type>", the type left empty by XLA
    ops = {k: (e.stats.get("tf_op", ""), e.stats.get("hlo_category"))
           for k, e in _ops(recorded).items()}
    assert ops["_fused_agg_blend.12"][0] == (
        "jit(global_round)/while/body/closed_call/jit(_fused_agg_blend)/"
        "pallas_call:")
    assert "vmap()/while" in ops["fusion.143"][0]
    assert ops["copy.58"] == (
        "jit(global_round)/while/body/closed_call/vmap()/while/body/"
        "closed_call/jvp()/reshape:", sc.RELAYOUT)
    assert ops["pad.88"][0].endswith("jit(_fused_agg_blend)/jit(_pad)/pad:")
    assert ops["copy-done.1"] == ("", "copy-done")


def test_a_trace_without_program_scopes_is_all_unscoped(recorded):
    s = tr.reduce_file(str(RECORDED))
    got = sc.reduce_planes(recorded)
    assert got["window_s"] == s["window_s"]
    assert got["busy_s"] == pytest.approx(s["busy_s"], rel=1e-12)
    assert got["device_s"] == {sc.UNSCOPED: pytest.approx(s["busy_s"])}
    assert got["idle_by_span_s"] == {
        sc.OUTSIDE: pytest.approx(s["window_s"] - s["busy_s"])}
    assert got["relayout_s"][sc.UNSCOPED] > 0.3 * s["busy_s"]
    assert got["spans"] == {}


# -- scopes, and idle time split by overlap with the host spans ------------

@pytest.mark.parametrize("tf_op, scope", [
    ("jit(global_round)/while/body/h2fed.local_train/vmap()/dot_general",
     "h2fed.local_train"),
    ("jit(f)/h2fed.local_train/vmap()/h2fed.local_train/jvp()/reshape",
     "h2fed.local_train"),
    ("jit(f)/h2fed.rsu_agg/jit(_fused_agg_blend)/pallas_call",
     "h2fed.rsu_agg"),
    ("jit(f)/h2fed.outer/h2fed.inner/add", "h2fed.inner"),
    ("jit(evaluate)/dot_general", sc.UNSCOPED),
    ("jit(f)/h2fed.draws/jit(_bernoulli)/slice:", "h2fed.draws"),
    ("jit(f)/h2fed.draws:", "h2fed.draws"),
    ("", sc.UNSCOPED),
])
def test_scope_is_the_innermost_program_scope(tf_op, scope):
    assert sc.scope_of(tf_op) == scope


SPANS = [("h2fed.round", 0.0, 4.0), ("h2fed.eval", 5.0, 9.0),
         ("h2fed.inner", 6.0, 7.0)]


def test_innermost_pieces_cover_the_window():
    assert sc.innermost(SPANS, (0.0, 10.0)) == [
        ("h2fed.round", 0.0, 4.0), (sc.OUTSIDE, 4.0, 5.0),
        ("h2fed.eval", 5.0, 6.0), ("h2fed.inner", 6.0, 7.0),
        ("h2fed.eval", 7.0, 9.0), (sc.OUTSIDE, 9.0, 10.0)]
    # clipped to a window that starts inside a span
    assert sc.innermost(SPANS, (2.0, 5.5)) == [
        ("h2fed.round", 2.0, 4.0), (sc.OUTSIDE, 4.0, 5.0),
        ("h2fed.eval", 5.0, 5.5)]
    assert sc.innermost([], (1.0, 2.0)) == [(sc.OUTSIDE, 1.0, 2.0)]


@pytest.mark.parametrize("gaps, want", [
    # inside the nested span
    ([(6.25, 6.75)], {"h2fed.inner": 0.5}),
    # across two spans and the time between them
    ([(3.0, 6.5)], {"h2fed.round": 1.0, sc.OUTSIDE: 1.0, "h2fed.eval": 1.0,
                    "h2fed.inner": 0.5}),
    # outside every span
    ([(9.25, 10.0)], {sc.OUTSIDE: 0.75}),
    # several gaps at once, the nested span entered and left
    ([(0.5, 1.0), (5.5, 7.5), (9.0, 9.5)],
     {"h2fed.round": 0.5, "h2fed.eval": 1.0, "h2fed.inner": 1.0,
      sc.OUTSIDE: 0.5}),
])
def test_idle_split_by_overlap_sums_to_the_gaps(gaps, want):
    got = sc.split_by_overlap(gaps, sc.innermost(SPANS, (0.0, 10.0)))
    assert got == pytest.approx(want)
    assert sum(got.values()) == pytest.approx(sum(e - s for s, e in gaps))


def _event(name, start, end, **stats):
    return sc.Event(name, start * 1e9, end * 1e9, stats)


def test_reduce_a_built_trace():
    # window [0, 10): training 0-3 (a relayout copy 1-2 nested in a loop
    # op), the kernel 3-4 under the RSU scope, an eval op 6-7 unscoped;
    # the host is in a round 0-4.5 and an eval 4.5-8
    loop = "jit(r)/while"
    dev = sc.Plane("/device:TPU:0", {tr.OPS_LINE: [
        _event("%while.1 = ...", 0, 3, tf_op=loop),
        _event("%fusion.1 = ...", 0, 1,
               tf_op=f"{loop}/body/h2fed.local_train/dot_general"),
        _event("%copy.1 = ...", 1, 2, hlo_category=sc.RELAYOUT,
               tf_op=f"{loop}/body/h2fed.local_train/reshape"),
        _event("%k.1 = ...", 3, 4,
               tf_op=f"{loop}/body/h2fed.rsu_agg/pallas_call"),
        _event("%fusion.2 = ...", 6, 7, tf_op="jit(evaluate)/dot_general"),
    ]})
    host = sc.Plane("/host:CPU", {"python3": [
        _event("bench.run_scenario", -1, 11),
        _event(tr.WINDOW_SPAN, 0, 10),
        _event("h2fed.round", 0, 4.5, round=1),
        _event("h2fed.eval", 4.5, 8, round=1),
    ]})
    got = sc.reduce_planes([dev, host])
    assert got["window_s"] == 10 and got["busy_s"] == pytest.approx(5)
    assert got["device_s"] == pytest.approx({
        "h2fed.local_train": 2, "h2fed.rsu_agg": 1, sc.UNSCOPED: 1 + 1})
    assert got["relayout_s"] == pytest.approx({"h2fed.local_train": 1})
    # idle 4-6 (round 0.5, eval 1.5) and 7-10 (eval 1, outside 2)
    assert got["idle_by_span_s"] == pytest.approx(
        {"h2fed.round": 0.5, "h2fed.eval": 2.5, sc.OUTSIDE: 2})
    assert got["idle_s"] == pytest.approx(5)
    assert got["spans"] == {"h2fed.round": 1, "h2fed.eval": 1}


# -- a trace recorded on a TPU v5e with the program's scopes and spans -----

SCOPED = DATA / "paper_csr10_scoped.xplane.pb"
PROGRAM_SCOPES = ("h2fed.local_train", "h2fed.rsu_agg", "h2fed.cloud_blend",
                  "h2fed.draws")


@pytest.fixture(scope="module")
def scoped():
    return sc.read_planes(str(SCOPED))


def test_scoped_trace_names_every_layer(scoped):
    got = sc.reduce_planes(scoped)
    assert set(got["device_s"]) == set(PROGRAM_SCOPES) | {sc.UNSCOPED}
    assert all(got["device_s"][s] > 0 for s in PROGRAM_SCOPES)
    # six rounds traced; the window ends inside the sixth round's eval
    assert got["spans"] == {"h2fed.round": 6, "h2fed.eval": 5}
    host = [e for p in scoped if p.name.startswith("/host:")
            for events in p.lines.values() for e in events]
    rounds = [e.stats["round"] for e in host if e.name == "h2fed.round"]
    evals = [e.stats["round"] for e in host if e.name == "h2fed.eval"]
    assert rounds[:6] == list(range(1, 7)) and evals[:5] == rounds[:5]


def test_scoped_device_time_adds_up_to_the_busy_time(scoped):
    s = tr.reduce_file(str(SCOPED))
    got = sc.reduce_planes(scoped)
    assert got["window_s"] == s["window_s"]
    assert sum(got["device_s"].values()) == pytest.approx(s["busy_s"],
                                                          rel=1e-9)
    assert sum(got["idle_by_span_s"].values()) == pytest.approx(
        s["window_s"] - s["busy_s"], rel=1e-9)
    scoped_s = s["busy_s"] - got["device_s"][sc.UNSCOPED]
    assert scoped_s > 0.85 * s["busy_s"]
    assert got["relayout_s"]["h2fed.local_train"] > 0.3 * s["busy_s"]
    assert set(got["idle_by_span_s"]) <= {"h2fed.round", "h2fed.eval",
                                          sc.OUTSIDE}


def test_both_aggregation_layers_launch_the_kernel_under_their_scope(
        scoped):
    """The kernel keeps its instruction name (``agg_kernels_ms`` finds it
    by that) and the scopes tell the five RSU launches of a round from
    its one cloud launch."""
    launches = {}
    for e in [p for p in scoped if tr.OPS_LINE in p.lines][0].lines[
            tr.OPS_LINE]:
        if tr.short_name(e.name).startswith("_fused_agg_blend."):
            scope = sc.scope_of(str(e.stats.get("tf_op", "")))
            launches[scope] = launches.get(scope, 0) + 1
    assert launches == {"h2fed.rsu_agg": 5 * 6, "h2fed.cloud_blend": 6}
