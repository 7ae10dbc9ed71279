"""``profiling.compile.op_scopes``: every instruction of every executable
``profiled_jit`` holds, with the ``jax.named_scope`` path it was traced
under, read out of ``compiled.as_text()`` when asked and not before; and the
scopes the step programs open (``prefill`` / ``labels`` / ``embed``,
``moe.dispatch`` / ``moe.matmul`` / ``moe.combine``, ``encoder.*``,
``histogram``), which ``perfbench/scope_parts.json`` sums a trace by."""

from __future__ import annotations

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(REPO, "perfbench") not in sys.path:
    sys.path.insert(0, os.path.join(REPO, "perfbench"))

import scope_reduce  # noqa: E402

from music_analyst_tpu.profiling import compile as program  # noqa: E402
from music_analyst_tpu.profiling.compile import (  # noqa: E402
    hlo_op_scopes,
    op_scopes,
    profiled_jit,
)

_WORDS = "love rain night baby tears dance road fire cold heart".split()


def _lyrics(rows: int = 6):
    rng = np.random.default_rng(4)
    return [" ".join(rng.choice(_WORDS, size=int(n)))
            for n in rng.integers(5, 300, size=rows)]


@pytest.fixture(scope="module", autouse=True)
def compiled_here():
    """Every test of this file reads scopes out of a compiled program's
    text, so every program it reads is compiled by it: a persistent
    compile cache (``utils/cache.py``: any test that enters through the
    CLI turns on ``<repo>/.jax_cache`` for its whole worker) keys an entry
    with the metadata left out, and hands back the executable of whichever
    tree wrote it, with that tree's scopes in its text."""
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture
def text_reads(monkeypatch):
    """The calls of ``compiled.as_text()`` made while the test runs."""
    calls = []
    as_text = jax.stages.Compiled.as_text

    def counted(self, *args, **kwargs):
        calls.append(self)
        return as_text(self, *args, **kwargs)

    monkeypatch.setattr(jax.stages.Compiled, "as_text", counted)
    return calls


def _scoped(x, w):
    with jax.named_scope("prefill"):
        with jax.named_scope("mla"):
            y = jnp.dot(x, w)

    def body(i, carry):
        with jax.named_scope("moe.experts"):
            return jnp.tanh(carry @ w) + i

    with jax.named_scope("labels"):
        y = jax.lax.fori_loop(0, 3, body, y)
        return jax.vmap(lambda row: jnp.sin(row).sum())(y)


def test_every_instruction_has_its_path_and_its_enclosing_while(text_reads):
    fn = profiled_jit(_scoped, name="op_scopes_probe")
    fn(jnp.ones((8, 8)), jnp.ones((8, 8)))
    assert text_reads == []          # compiling reads no text
    (entry,) = fn.op_scopes()
    assert len(text_reads) == 1
    assert (entry["fn"], entry["module"]) == ("op_scopes_probe", "jit__scoped")
    assert entry["aval_key"] == next(iter(fn.records))
    ops = entry["ops"]
    (loop,) = {inside for _, inside in ops.values() if inside}
    # the loop itself, in the entry computation
    assert ops[loop] == ["jit(_scoped)/labels/while", None]
    paths = {path for path, _ in ops.values()}
    assert "jit(_scoped)/prefill/mla/dot_general" in paths
    assert any(p.startswith("jit(_scoped)/labels/vmap()/") for p in paths)
    body = {name: path for name, (path, inside) in ops.items()
            if inside == loop}
    assert ("jit(_scoped)/labels/while/body/closed_call/moe.experts/"
            "dot_general") in body.values()
    assert "jit(_scoped)/labels/while/cond/lt" in body.values()
    # those XLA printed no path for (tuples, the copies it adds, the
    # body's parameter) stand under an operand's or the loop's
    assert all(path.startswith("jit(_scoped)/labels/while")
               for path in body.values())
    # inside a fused computation nothing is listed: one operation on the chip
    assert not any(name.startswith("tanh") for name in ops)

    # kept: the whole process's map reads this program's text no second time
    assert any(e is entry for e in op_scopes())
    assert fn.op_scopes() == [entry]
    (executable,) = fn._compiled.values()
    assert [read for read in text_reads if read is executable] == [executable]


def test_a_function_that_fell_back_is_listed_with_no_map(monkeypatch):
    fn = profiled_jit(lambda x: x + 1, name="fell_back_probe")
    monkeypatch.setattr(fn, "_compile_for", lambda *a, **k: None)
    assert float(fn(jnp.zeros(()))) == 1.0
    (entry,) = fn.op_scopes()
    assert entry == {"fn": "fell_back_probe", "module": None, "ops": None,
                     "aval_key": entry["aval_key"]}
    assert entry in op_scopes()


_TPU_TEXT = """HloModule jit__step, is_scheduled=true, entry_computation_layout={()}

%fused_computation (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %hidden.1 = f32[8]{0} negate(%p), metadata={op_name="jit(_step)/prefill/neg"}
}

%branch_a (t: (f32[8])) -> (f32[8]) {
  %t = (f32[8]{0}) parameter(0)
  %in_branch.1 = f32[8]{0} get-tuple-element(%t), index=0
  ROOT %out.1 = (f32[8]{0}) tuple(%in_branch.1)
}

%called (c: f32[8]) -> f32[8] {
  %c = f32[8]{0} parameter(0)
  ROOT %in_call.1 = f32[8]{0} copy(%c)
}

ENTRY %main.1 (x: f32[8], w: f32[4,8,8]) -> f32[8] {
  %x = f32[8]{0} parameter(0), metadata={op_name="x"}
  %w = f32[4,8,8]{2,1,0} parameter(1), metadata={op_name="params['experts']"}
  %copy.9 = f32[4,8,8]{2,1,0} copy(%w)
  %fusion.3 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(_step)/prefill/moe.experts/moe.dispatch/gather"}
  %ragged-dot-none.2 = f32[8]{0} custom-call(%copy.9, /*index=1*/%fusion.3), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}, backend_config={"body":"(x)"}
  %tuple.4 = (f32[8]{0}) tuple(%ragged-dot-none.2)
  %cond.5 = (f32[8]{0}) conditional(%fusion.3, %tuple.4, %tuple.4), branch_computations={%branch_a, %branch_a}, metadata={op_name="jit(_step)/labels/cond"}
  %call.6 = f32[8]{0} call(%x), to_apply=%called, metadata={op_name="jit(_step)/labels/closed_call"}
  ROOT %add.7 = f32[8]{0} add(%call.6, %ragged-dot-none.2), metadata={op_name="jit(_step)/labels/add"}
}
"""


def test_a_name_of_xlas_own_stands_under_its_operands_path():
    """The TPU compiler rewrites ``ragged_dot`` into its grouped-matmul
    kernel and prints ``op_name="ragged-dot-none"`` for every one of them:
    the map puts it under the path of its first operand that has one, and
    what has no such operand under its first user's."""
    module, ops = hlo_op_scopes(_TPU_TEXT)
    assert module == "jit__step"
    gather = "jit(_step)/prefill/moe.experts/moe.dispatch/gather"
    assert ops["ragged-dot-none.2"] == [gather + "/ragged-dot-none", None]
    assert ops["tuple.4"] == [gather + "/ragged-dot-none", None]
    # a parameter keeps its argument's name; the copy XLA added between it
    # and the kernel has no operand with a path: its user's
    assert ops["w"] == ["params['experts']", None]
    assert ops["copy.9"] == [gather + "/ragged-dot-none", None]
    # a conditional's branches and a call's computation are followed, a
    # fusion's is not
    assert ops["in_branch.1"] == ["jit(_step)/labels/cond", "cond.5"]
    assert ops["in_call.1"] == ["jit(_step)/labels/closed_call", "call.6"]
    assert "hidden.1" not in ops and "p" not in ops
    table = scope_reduce.load_parts()["jit__score_labels"]
    assert scope_reduce.part_of(ops["ragged-dot-none.2"][0], table) == (
        "prefill.matmul")
    assert scope_reduce.part_of(ops["fusion.3"][0], table) == (
        "prefill.dispatch")


def _backend(name):
    from music_analyst_tpu.engines.sentiment import get_backend

    return get_backend(name)


def _components(entries):
    return [set(scope_reduce.components(path))
            for entry in entries for path, _ in entry["ops"].values()
            if path.startswith("jit(")]


@pytest.mark.parametrize("model,both,within", [
    ("kanana-tiny", ("mla", "moe.route", "moe.shared", "lm_head", "embed",
                     "moe.dispatch", "moe.matmul", "moe.combine"), ()),
    ("ling-tiny", ("kda.proj", "kda.chunk", "kda.out", "mla",
                   "moe.dispatch", "moe.matmul", "moe.combine"), ("while",)),
])
def test_a_scoring_program_tells_prefill_from_labels(model, both, within):
    """Every traced operation of ``jit__score_labels`` stands under
    ``prefill`` or ``labels``, each part of a layer under both, and none
    falls through ``scope_parts.json``'s rows."""
    served = _backend(model)
    served.classify_batch(_lyrics())
    entries = served._score_labels.op_scopes()
    assert entries and all(e["module"] == "jit__score_labels"
                           for e in entries)
    found = _components(entries)
    for phase in ("prefill", "labels"):
        here = set().union(*(c for c in found if phase in c))
        assert set(both) | set(within if phase == "labels" else ()) <= here
    assert all(("prefill" in c) != ("labels" in c) for c in found)
    assert all({"moe.dispatch", "moe.matmul", "moe.combine"} & c
               or not c & {"sort", "ragged_dot"}
               for c in found if "moe.experts" in c)
    table = scope_reduce.load_parts()["jit__score_labels"]
    parts = {scope_reduce.part_of(path, table)
             for entry in entries for path, _ in entry["ops"].values()
             if path.startswith("jit(")}
    assert scope_reduce.OTHER not in parts
    assert {"labels.dispatch", "labels.matmul", "labels.combine",
            "prefill.dispatch", "prefill.matmul", "prefill.combine",
            "prefill.embed", "prefill.head", "labels.head"} <= parts


def test_the_diffusion_programs_keep_their_scopes():
    served = _backend("sdar-tiny")
    served.classify_batch(_lyrics(4))
    prefill = _components(served._prefill.op_scopes())
    denoise = _components(served._denoise.op_scopes())
    assert {e["module"] for e in served._denoise.op_scopes()} == {
        "jit__diffusion_denoise"}
    here = set().union(*(c for c in prefill if "diffusion.prefill" in c))
    assert {"embed", "gqa", "moe.dispatch", "moe.matmul", "moe.combine",
            "moe.route"} <= here
    for phase in ("diffusion.denoise", "diffusion.commit"):
        here = set().union(*(c for c in denoise if phase in c))
        assert {"gqa", "moe.dispatch", "moe.matmul", "moe.combine"} <= here
    assert any("diffusion.unmask" in c for c in denoise)
    assert any({"diffusion.denoise", "lm_head"} <= c for c in denoise)


def test_the_encoder_step_and_the_histogram_have_their_scopes():
    from music_analyst_tpu.ops import histogram
    from music_analyst_tpu.parallel.mesh import data_parallel_mesh

    served = _backend("distilbert-tiny")
    served.classify_batch(_lyrics())
    entries = served._forward.op_scopes()
    assert {e["module"] for e in entries} == {"jit__forward"}
    found = _components(entries)
    for scope in ("encoder.embed", "encoder.attention", "encoder.ffn",
                  "encoder.head"):
        assert any(scope in c for c in found), scope
    table = scope_reduce.load_parts()["jit__forward"]
    assert {scope_reduce.part_of(path, table) for entry in entries
            for path, _ in entry["ops"].values()
            if "dot_general" in path} == {
        "encoder.attention", "encoder.ffn", "encoder.head"}

    accumulate = histogram._stream_accum(data_parallel_mesh(1), "dp", 1024)
    accumulate(np.zeros((1, 1024), np.int32), np.zeros((4096,), np.int32))
    (entry,) = [e for e in accumulate.op_scopes()]
    assert entry["module"] == "jit_local"
    assert any("histogram" in c for c in _components([entry]))


def test_profile_run_writes_the_map_beside_the_spans(tmp_path):
    from music_analyst_tpu.profiling.trace import profile_run

    fn = profiled_jit(_scoped, name="profile_run_probe")
    with profile_run(str(tmp_path / "prof")):
        fn(jnp.ones((4, 4)), jnp.ones((4, 4)))
    assert (tmp_path / "prof" / "trace_spans.json").exists()
    written = json.loads((tmp_path / "prof" / "op_scopes.json").read_text())
    (entry,) = [e for e in written if e["fn"] == "profile_run_probe"]
    assert entry["module"] == "jit__scoped"
    assert ["jit(_scoped)/prefill/mla/dot_general", None] in (
        entry["ops"].values())
    with profile_run(str(tmp_path / "spans_only"), device_trace=False):
        pass
    assert not (tmp_path / "spans_only" / "op_scopes.json").exists()


def test_a_job_nobody_traces_reads_no_text_and_writes_nothing_new(
        tmp_path, fixture_csv, text_reads, monkeypatch):
    from music_analyst_tpu.cli.main import main

    asked = []
    monkeypatch.setattr(program, "op_scopes", lambda: asked.append(1) or [])
    out = tmp_path / "out"
    assert main(["sentiment", str(fixture_csv), "--model", "kanana-tiny",
                 "--output-dir", str(out), "--batch-size", "4"]) == 0
    assert text_reads == [] and asked == []
    manifest = json.loads((out / "run_manifest.json").read_text())
    compiles = manifest["profiling"]["compiles"]
    assert compiles and all(set(record) == {
        "name", "aval_key", "flops", "bytes_accessed", "temp_bytes",
        "argument_bytes", "output_bytes", "hlo_fingerprint",
        "compile_seconds", "param_bytes", "attention_paths",
        "traced_paths"} for record in compiles)
    assert "op_scopes" not in manifest
    assert set(manifest["profiling"]) == {"scope", "compiles"}
    # no instruction's path anywhere in what the job wrote
    assert "jit(_score_labels)" not in (
        (out / "run_manifest.json").read_text()
        + (out / "telemetry.jsonl").read_text())
    assert sorted(p.name for p in out.iterdir()) == [
        "run_manifest.json", "sentiment_details.csv",
        "sentiment_totals.json", "telemetry.jsonl"]
