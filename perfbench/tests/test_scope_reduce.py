"""The reduction of a trace by the program's scopes (``scope_reduce.py``) on
traces made here from text (``ProfileData.from_text_proto``) with known
answers: a ``while`` event with its body's events inside it, two programs
that share operation names, an event across the window's edge, a name no
map holds, one function compiled at several shapes; and on the trace
recorded on a TPU v5e (``small.xplane.pb``) with an empty map."""

import json
import os

import pytest
from jax.profiler import ProfileData

import scope_reduce as sr
import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))

LABELS = "jit(_score_labels)/labels/vmap(LlamaModel)/layer_1/"
# (name, start_ns, end_ns): a loop over the labels with two trips of its
# body, then an operation of the prefill; a second program with the same
# names, a stranger and an operation that outlasts the window (to 15,000)
SCORE_OPS = [("%while.1 = (s32[]) while(%tuple.1), body=%b", 1000, 7000),
             ("%fusion.1 = f32[8] fusion(%p.1)", 1500, 2500),
             ("%fusion.2 = f32[8] fusion(%p.2)", 3000, 5000),
             ("%fusion.1 = f32[8] fusion(%p.1)", 5200, 6200),
             ("%fusion.3 = f32[8] fusion(%p.3)", 7500, 8500)]
FORWARD_OPS = [("%fusion.1 = f32[4] fusion(%q.1)", 10000, 12000),
               ("%stranger.7 = f32[4] copy(%q.2)", 12000, 13000),
               ("%fusion.2 = f32[4] fusion(%q.3)", 14000, 16000)]
MODULES = [("jit__score_labels(11)", 1000, 9000),
           ("jit__forward(22)", 10000, 16000)]
WINDOW = (500, 15000)
SCORE_MAP = {
    "while.1": ["jit(_score_labels)/labels/while", None],
    "fusion.1": [LABELS + "moe.experts/moe.dispatch/sort", "while.1"],
    "fusion.2": [LABELS + "mla/attention/dot_general", "while.1"],
    "fusion.3": ["jit(_score_labels)/prefill/LlamaModel/layer_0/mla/exp",
                 None],
}
FORWARD_MAP = {
    "fusion.1": ["jit(_forward)/layer_0/encoder.ffn/dot_general", None],
    "fusion.2": ["jit(_forward)/layer_0/encoder.attention/add", None],
}
SCOPES = [
    {"fn": "llama_score_labels", "aval_key": "a", "ops": SCORE_MAP,
     "module": "jit__score_labels"},
    {"fn": "distilbert_forward", "aval_key": "b", "ops": FORWARD_MAP,
     "module": "jit__forward"},
    {"fn": "fell_back", "aval_key": "c", "ops": None, "module": None},
]


def _line(name, events, ids):
    rows = "".join(
        f"events {{ metadata_id: {ids.setdefault(n, len(ids) + 1)} "
        f"offset_ps: {s * 1000} duration_ps: {(e - s) * 1000} }}\n"
        for n, s, e in events)
    return f'lines {{ name: "{name}" timestamp_ns: 0\n{rows}}}\n'


def _names(ids):
    return "".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
        for n, i in ids.items())


def xspace_text(ops, modules, window=WINDOW) -> str:
    ids = {}
    device = (_line(tr.OPS_LINE, ops, ids)
              + _line(tr.MODULES_LINE, modules, ids))
    lo, hi = window
    return (
        f'planes {{ id: 1 name: "/device:TPU:0"\n{device}{_names(ids)}}}\n'
        'planes { id: 2 name: "/host:CPU"\n'
        'lines { name: "python3" timestamp_ns: 0\n'
        f"events {{ metadata_id: 1 offset_ps: {lo * 1000} "
        f"duration_ps: {(hi - lo) * 1000} "
        "stats { metadata_id: 1 int64_value: 77 } } }\n"
        f'event_metadata {{ key: 1 value {{ id: 1 name: "{tr.WINDOW_NAME}" '
        "} }\n"
        'stat_metadata { key: 1 value { id: 1 name: "mono_ns" } } }\n')


def _ns(seconds: float) -> int:
    return round(seconds * 1e9)


@pytest.fixture(scope="module")
def profile():
    return ProfileData.from_text_proto(
        xspace_text(SCORE_OPS + FORWARD_OPS, MODULES))


@pytest.fixture(scope="module")
def reduced(profile):
    return sr.reduce_profile(profile, SCOPES, sr.load_parts())


def test_self_times_add_up_to_the_union_whatever_the_overlaps():
    nested = [(0, 100), (10, 30), (15, 20), (40, 90), (90, 100)]
    assert sr.self_times(nested) == [20, 15, 5, 50, 10]
    # not nested: the later one owns the overlap, nothing counts twice
    assert sr.self_times([(0, 50), (30, 80), (200, 210), (5, 5)]) == [
        30, 50, 10, 0]
    assert sr.self_times([]) == []


def test_self_times_add_up_to_the_busy_union_to_the_nanosecond(
        profile, reduced):
    busy = tr.reduce_profile(profile)["busy_s"]
    assert _ns(busy) == 6000 + 1000 + 3000 + 1000
    assert _ns(reduced["busy_s"]) == _ns(busy)
    assert _ns(sum(m["seconds"] for m in reduced["modules"].values())
               ) == _ns(busy)


def test_a_while_keeps_its_self_time_and_its_body_its_own(reduced):
    parts = {part: _ns(s) for part, s in
             reduced["modules"]["jit__score_labels"]["parts"].items()}
    # the loop: 6,000 long, 4,000 of it its body's two trips
    assert parts == {"labels.other": 2000, "labels.dispatch": 2000,
                     "labels.mla": 2000, "prefill.mla": 1000}
    assert reduced["modules"]["jit__score_labels"]["executions"] == 1


def test_two_programs_with_the_same_names_are_kept_apart(profile, reduced):
    merged = dict(tr.reduce_profile(profile)["device_ops"])
    assert _ns(merged["fusion.1"]) == 4000   # trace_reduce sums by name
    forward = {part: _ns(s) for part, s in
               reduced["modules"]["jit__forward"]["parts"].items()}
    # fusion.2 ends 1,000 behind the window: cut to it
    assert forward == {"encoder.ffn": 2000, "encoder.attention": 1000,
                       sr.UNMAPPED: 1000}
    top = {(op["module"], op["op"]): op for op in reduced["top_ops"]}
    assert top[("jit__score_labels", "fusion.1")]["part"] == "labels.dispatch"
    assert top[("jit__forward", "fusion.1")]["part"] == "encoder.ffn"
    assert top[("jit__forward", "stranger.7")]["op_name"] == ""


def test_the_stranger_is_counted_as_unmapped(reduced):
    assert _ns(reduced["unmapped_s"]) == 1000


def test_a_program_no_map_names_is_unmapped_and_still_counted(profile):
    alone = sr.reduce_profile(profile, SCOPES[:1], sr.load_parts())
    assert _ns(alone["busy_s"]) == 11000
    assert _ns(alone["unmapped_s"]) == 4000
    assert alone["modules"]["jit__forward"]["parts"] == {
        sr.UNMAPPED: pytest.approx(4e-6)}


def test_of_several_compiled_shapes_the_one_that_holds_every_name(profile):
    other_shape = {"fusion.1": ["jit(_score_labels)/prefill/embed/take", None],
                   "while.1": SCORE_MAP["while.1"]}
    scopes = [dict(SCOPES[0], ops=other_shape, aval_key="z")] + SCOPES
    got = sr.reduce_profile(profile, scopes, sr.load_parts())
    assert {p: _ns(s) for p, s in
            got["modules"]["jit__score_labels"]["parts"].items()} == {
        "labels.other": 2000, "labels.dispatch": 2000, "labels.mla": 2000,
        "prefill.mla": 1000}
    # two shapes hold every name and place one of them differently
    twin = dict(SCORE_MAP, **{
        "fusion.2": ["jit(_score_labels)/prefill/lm_head/dot_general", None]})
    scopes = [dict(SCOPES[0], ops=twin, aval_key="y")] + SCOPES
    got = sr.reduce_profile(profile, scopes, sr.load_parts())
    parts = got["modules"]["jit__score_labels"]["parts"]
    assert _ns(parts[sr.AMBIGUOUS]) == 2000 and "labels.mla" not in parts
    assert _ns(got["unmapped_s"]) == 3000


def test_components_and_parts():
    path = LABELS + "moe.experts/moe.dispatch/scatter-add/ragged-dot-none"
    assert sr.components("jit(f)/vmap(jit(_pad))/transpose(jvp(mla))/exp"
                         ) == ["f", "_pad", "mla", "exp"]
    table = sr.load_parts()["jit__score_labels"]
    # the TPU compiler's grouped matmul stands under its operand's path
    assert sr.part_of(path, table) == "labels.matmul"
    assert sr.part_of(path.rsplit("/", 1)[0], table) == "labels.dispatch"
    assert sr.part_of("jit(_score_labels)/prefill/LlamaModel/embed/gather",
                      table) == "prefill.embed"
    assert sr.part_of("params['lm_head']", table) == sr.OTHER
    assert sr.kind("prefill.kda.proj") == "kda.proj"
    assert sr.module_name("jit__forward(123)") == "jit__forward"


def test_the_recorded_trace_with_an_empty_map_is_all_unmapped():
    path = os.path.join(HERE, "small.xplane.pb")
    got = sr.reduce_profile(ProfileData.from_file(path), [], sr.load_parts())
    busy = tr.reduce_file(path)["busy_s"]
    assert _ns(got["busy_s"]) == _ns(busy) == 47431
    assert _ns(got["unmapped_s"]) == _ns(busy)
    assert list(got["modules"]) == ["jit__lambda"]
    assert got["modules"]["jit__lambda"]["executions"] == 4


def test_a_trace_without_a_device_plane_reduces_to_nothing():
    host_only = ProfileData.from_text_proto(
        'planes { id: 2 name: "/host:CPU" }')
    assert sr.reduce_profile(host_only, SCOPES, sr.load_parts()) is None


@pytest.fixture
def run_artifacts(tmp_path, monkeypatch):
    """A run's directories as the drivers leave them, with the trace made
    above where the profiler would have written one."""
    from music_analyst_tpu.profiling import compile as program

    part = tmp_path / "cell" / "run" / "job0" / "sentiment"
    trace = tmp_path / "cell" / "run" / "trace" / "plugins" / "profile" / "t0"
    part.mkdir(parents=True)
    trace.mkdir(parents=True)
    (trace / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(
            xspace_text(SCORE_OPS + FORWARD_OPS, MODULES)))
    calls = []
    monkeypatch.setattr(program, "op_scopes",
                        lambda: calls.append(1) or SCOPES)
    return {"trace": {"busy_s": 11e-6}, "calls": calls,
            "jobs": [{"parts": {"sentiment": {"dir": str(part)}}}]}


def test_the_readers_give_the_shares_as_constructed(run_artifacts, tmp_path,
                                                    capsys):
    from layer_metrics import (encoder_ffn_time_share,
                               expert_dispatch_time_share, expert_time_share,
                               label_pass_time_share, scope_unmapped_share)

    assert label_pass_time_share.read(run_artifacts) == pytest.approx(
        100 * 6000 / 7000)
    assert expert_time_share.read(run_artifacts) == pytest.approx(
        100 * 2000 / 7000)
    assert expert_dispatch_time_share.read(run_artifacts) == pytest.approx(
        100 * 2000 / 7000)
    assert encoder_ffn_time_share.read(run_artifacts) == pytest.approx(50.0)
    assert scope_unmapped_share.read(run_artifacts) == pytest.approx(
        100 * 1000 / 11000)
    assert run_artifacts["calls"] == [1]   # one reduction a run
    with open(tmp_path / "cell" / "scope_reduced.json") as fh:
        written = json.load(fh)
    assert _ns(written["busy_s"]) == 11000
    assert set(written["cost"]) == {"op_scopes_s", "reduce_s"}
    capsys.readouterr()

    from tools import scope_table

    assert scope_table.main([str(tmp_path / "cell")]) == 0
    table = capsys.readouterr().out
    assert "jit__score_labels: 0.000 s in 1 executions" in table
    assert "labels.dispatch" in table and "stranger.7" in table


@pytest.mark.parametrize("missing", ["trace", "jobs", "xplane", "op_scopes"])
def test_a_reader_returns_none_and_never_raises(run_artifacts, tmp_path,
                                                monkeypatch, missing):
    from layer_metrics import label_pass_time_share, scope_unmapped_share
    from music_analyst_tpu.profiling import compile as program

    if missing == "xplane":
        for found in (tmp_path / "cell" / "run" / "trace").rglob("*.pb"):
            found.unlink()
    elif missing == "op_scopes":   # the parent's program has no such name
        monkeypatch.delattr(program, "op_scopes")
    else:
        run_artifacts[missing] = None
    assert label_pass_time_share.read(run_artifacts) is None
    assert scope_unmapped_share.read(run_artifacts) is None
    assert not os.path.exists(tmp_path / "cell" / "scope_reduced.json")
