"""The op-metadata reader of profiler traces, on intervals made up here
and on two small traces recorded on a v5e chip: `small` from before the
fused kernel had stage scopes, `staged` from an engine with them and
with its pass spans (each an n=64 engine job of 3000 events in
512-event syncs under the harness's profiler options, recorded by
benchmark/record_trace.py)."""

import lzma
import os
import types

import pytest

from benchmark import run as harness
from benchmark import xplane_ops as xo
from benchmark.record_trace import OPS_METRICS

DATA = os.path.join(os.path.dirname(__file__), "data")


def unpacked(tmp_path_factory, name):
    path = tmp_path_factory.mktemp("trace") / (name + ".xplane.pb")
    with lzma.open(os.path.join(DATA, name + ".xplane.pb.xz")) as src:
        path.write_bytes(src.read())
    return str(path)


@pytest.fixture(scope="module")
def small(tmp_path_factory):
    return unpacked(tmp_path_factory, "small")


@pytest.fixture(scope="module")
def staged(tmp_path_factory):
    return unpacked(tmp_path_factory, "staged")


def test_stage_is_the_first_scope_under_the_fused_kernel():
    assert xo.stage_of("jit(_consensus_fused)/fame/while/body/eq:") == "fame"
    assert xo.stage_of("jit(_consensus_fused)/jit(clip)/min:") == "unscoped"
    assert xo.stage_of("jit(_closure_update)/while/body/gather:") == (
        "unscoped")


def test_leaves_and_inherited_scopes():
    tf_op = {1: "jit(_consensus_fused)/fame/while",
             2: "jit(_consensus_fused)/fame/while/body/eq",
             3: "",
             4: "jit(_consensus_fused)/pack/concatenate"}
    ops = [(0, 100, 1), (10, 20, 2), (30, 50, 3), (100, 110, 4),
           (120, 125, 3)]
    assert sorted(xo.leaf_stages(ops, tf_op)) == [
        (10, 20, "fame"), (30, 50, "fame"), (100, 110, "pack"),
        (120, 125, "unscoped")]


def test_idle_overlap_of_a_span():
    idle = [(0, 10), (20, 30), (40, 50)]
    starts = [s for s, _ in idle]
    assert xo.overlap(idle, starts, 5, 45) == 5 + 10 + 5
    assert xo.overlap(idle, starts, 10, 20) == 0


@pytest.mark.parametrize("name", ["small", "staged"])
def test_reader_matches_profile_data(request, name):
    import jax

    path = request.getfixturevalue(name)
    mine = {p.name: p for p in xo.read_planes(path)}
    pd = jax.profiler.ProfileData.from_file(path)
    checked = 0
    for plane in pd.planes:
        names = [line.name for line in plane.lines]
        for line in plane.lines:
            if names.count(line.name) > 1:
                continue  # merged by name in the reader
            got = mine[plane.name].lines.get(line.name, [])
            events = list(line.events)
            assert len(got) == len(events)
            for ev, (s, e, mid) in zip(events, got):
                assert mine[plane.name].names[mid] == ev.name
                assert abs(s - ev.start_ns) < 1
                assert abs((e - s) - ev.duration_ns) < 1
            checked += len(events)
    assert checked > 40_000


def test_recorded_trace_before_the_stage_scopes(small):
    dev, = [p for p in xo.read_planes(small)
            if p.name == "/device:TPU:0"]
    fused = [t for t in dev.tf_op.values()
             if t.startswith("jit(_consensus_fused)/")]
    assert len(fused) > 100
    got = xo.summarize(small)
    assert set(got.fused_stages_s) == {"unscoped"}
    # Leaf ops cover the module time (while ops enclose their bodies).
    leaves = sum(got.fused_stages_s.values())
    assert 0.95 * got.fused_module_s <= leaves <= got.fused_module_s


def test_recorded_trace_with_the_stage_scopes(staged):
    got = xo.summarize(staged)
    assert got.devices == 1
    assert all(got.fused_stages_s.get(s, 0) > 0 for s in xo.STAGES)
    leaves = sum(got.fused_stages_s.values())
    assert 0.95 * got.fused_module_s <= leaves <= got.fused_module_s
    assert got.idle_in_span_s["engine.apply"] > 0
    assert got.idle_in_span_s["engine.stage"] > 0
    # Nested spans: the collect holds the apply, so its idle is larger.
    assert got.idle_in_span_s["engine.collect"] >= (
        got.idle_in_span_s["engine.apply"])
    run = types.SimpleNamespace(trace_ops=got, counters={"passes": 7})
    values = {m: harness.metric_reader(m)(run) for m in OPS_METRICS}
    assert all(v is not None and v >= 0 for v in values.values())
    assert values["fused_stage_ms_per_pass.fame"] == pytest.approx(
        got.fused_stages_s["fame"] / 7 * 1e3)
