"""The bounded-depth prefetch pipeline's contracts (runtime/prefetch.py +
runtime/wire.py): ordering at every depth, prompt failure propagation
(a raising stage can never hang the run), cancellation that drains and
joins, bounded backpressure, real overlap, wire narrowing round-trips,
and byte-identical engine artifacts with the pipeline on or off
(the SURVEY §5 golden contract, ISSUE 3 acceptance).
"""

import json
import threading
import time

import numpy as np
import pytest

from music_analyst_tpu.runtime import (
    DEFAULT_PREFETCH_DEPTH,
    PrefetchPipeline,
    Stage,
    count_h2d_bytes,
    narrow_lengths,
    pack_mask,
    resolve_prefetch_depth,
    unpack_mask,
)


# --------------------------------------------------------------- executor


@pytest.mark.parametrize("depth", [0, 1, 3])
def test_results_in_source_order(depth):
    pipe = PrefetchPipeline(
        [Stage("double", lambda x: x * 2), Stage("inc", lambda x: x + 1)],
        depth=depth,
    )
    assert list(pipe.run(iter(range(57)))) == [x * 2 + 1 for x in range(57)]


def test_multiworker_stage_keeps_order():
    # Uneven per-item latency would scramble results if the window didn't
    # flush in submission order.
    def jittery(x):
        time.sleep(0.001 * (x % 3))
        return x * x

    pipe = PrefetchPipeline([Stage("sq", jittery, workers=4)], depth=2)
    assert list(pipe.run(iter(range(40)))) == [x * x for x in range(40)]


def test_stage_exception_propagates_promptly():
    def boom(x):
        if x == 5:
            raise RuntimeError("stage blew up")
        return x

    pipe = PrefetchPipeline([Stage("t", boom)], depth=2)
    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match="stage blew up"):
        list(pipe.run(iter(range(10_000))))
    # "Promptly": nothing waited out a queue timeout chain or a join.
    assert time.perf_counter() - t0 < 2.0


def test_source_exception_propagates():
    def bad_source():
        yield 1
        raise ValueError("source died")

    pipe = PrefetchPipeline([Stage("id", lambda x: x)], depth=1)
    with pytest.raises(ValueError, match="source died"):
        list(pipe.run(bad_source()))


def test_consumer_close_cancels_and_joins():
    before = {t.ident for t in threading.enumerate()}
    pipe = PrefetchPipeline([Stage("id", lambda x: x)], depth=2)
    gen = pipe.run(iter(range(100_000)))
    assert next(gen) == 0
    gen.close()  # early exit: must cancel, drain, and join the threads
    deadline = time.time() + 6.0
    while time.time() < deadline:
        alive = [
            t for t in threading.enumerate()
            if t.ident not in before and t.is_alive()
        ]
        if not alive:
            break
        time.sleep(0.01)
    assert not alive, f"pipeline threads leaked: {alive}"


def test_backpressure_bounds_source_readahead():
    pulled = []

    def source():
        for i in range(1000):
            pulled.append(i)
            yield i

    pipe = PrefetchPipeline([Stage("id", lambda x: x)], depth=2)
    gen = pipe.run(source())
    next(gen)
    time.sleep(0.3)  # producer side runs free; consumer holds back
    # Bound: depth items in each of 2 queues + one in-hand per thread.
    assert len(pulled) <= 2 * 2 + 3, pulled
    gen.close()


def test_overlap_reduces_wall_time():
    def slow_source():
        for i in range(10):
            time.sleep(0.015)
            yield i

    def slow_stage(x):
        time.sleep(0.015)
        return x

    def wall(depth):
        pipe = PrefetchPipeline([Stage("s", slow_stage)], depth=depth)
        t0 = time.perf_counter()
        assert list(pipe.run(slow_source())) == list(range(10))
        return time.perf_counter() - t0

    serial, overlapped = wall(0), wall(2)
    # Perfect overlap halves it; generous margin for a loaded CI box.
    assert overlapped < serial * 0.8, (serial, overlapped)


def test_stats_and_summary_shape():
    pipe = PrefetchPipeline(
        [Stage("a", lambda x: x)], depth=2, name="p", sink_name="sink"
    )
    list(pipe.run(iter(range(8))))
    summary = pipe.summary()
    assert summary["depth"] == 2
    names = [s["stage"] for s in summary["stages"]]
    assert names == ["source", "a", "sink"]
    a = summary["stages"][1]
    assert a["items"] == 8
    for key in ("work_s", "stall_s", "backpressure_s", "queue_depth_max"):
        assert key in a
    assert summary["max_queue_depth"] >= 0


def test_resolve_prefetch_depth(monkeypatch):
    monkeypatch.delenv("MUSICAAL_PREFETCH_DEPTH", raising=False)
    assert resolve_prefetch_depth(None) == DEFAULT_PREFETCH_DEPTH
    assert resolve_prefetch_depth(0) == 0
    assert resolve_prefetch_depth("3") == 3
    monkeypatch.setenv("MUSICAAL_PREFETCH_DEPTH", "1")
    assert resolve_prefetch_depth(None) == 1
    assert resolve_prefetch_depth(4) == 4  # explicit arg beats env
    with pytest.raises(ValueError):
        resolve_prefetch_depth(-1)
    with pytest.raises(ValueError):
        resolve_prefetch_depth("two")


def test_pipeline_publishes_telemetry():
    from music_analyst_tpu.telemetry import configure

    tel = configure(enabled=True, directory=None)
    pipe = PrefetchPipeline(
        [Stage("tokenize", lambda x: x), Stage("h2d", lambda x: x)],
        depth=2, name="pipeline", sink_name="compute",
    )
    list(pipe.run(iter(range(5))))
    assert "pipeline.h2d_stall_s" in tel.gauges
    assert "pipeline.compute_stall_s" in tel.gauges
    recorded = tel.pipeline_summary()["pipeline"]
    assert [s["stage"] for s in recorded["stages"]] == [
        "source", "tokenize", "h2d", "compute",
    ]
    # The key only appears in the compact digest when a pipeline ran
    # (bench contract pins the pipeline-free three-key shape).
    assert "pipeline" in tel.summary()


@pytest.mark.parametrize("depth", [2, 0])
def test_pipeline_records_read_and_wait_per_item(depth):
    """One ``read`` and one ``wait`` span per item with its index, every
    span of the pipeline under the span open in the caller, each at its
    true start (a ``workers > 1`` stage accounts a result when it leaves
    the window, later than the work ended)."""
    from music_analyst_tpu.telemetry import configure

    tel = configure(enabled=True, directory=None)
    started = {}

    def slow(item):
        started[item[0]] = time.monotonic()
        time.sleep(0.03 if item[0] % 2 else 0.005)
        return item

    pipe = PrefetchPipeline(
        [
            Stage("tokenize", lambda x: x),
            Stage("slow", slow, workers=2),
            Stage("h2d", lambda x: x),
        ],
        depth=depth, name="pipeline", sink_name="compute",
    )
    with tel.span("caller") as caller:
        out = list(pipe.run([i] * (i + 1) for i in range(5)))
    assert [item[0] for item in out] == list(range(5))

    by_name = {}
    for sp in tel.spans:
        by_name.setdefault(sp.name, []).append(sp)
    for name in ("read", "wait", "tokenize", "slow", "h2d"):
        spans = by_name[name]
        assert [sp.attrs["seq"] for sp in spans] == list(range(5)), name
        assert all(sp.parent_id == caller.span_id for sp in spans), name
        assert all(sp.attrs["pipeline"] == "pipeline" for sp in spans)
    assert [sp.attrs["rows"] for sp in by_name["read"]] == [1, 2, 3, 4, 5]
    assert all("rows" not in sp.attrs for sp in by_name["wait"])
    for read, tokenize in zip(by_name["read"], by_name["tokenize"]):
        assert read.t_mono + read.duration_s <= tokenize.t_mono
    # the span starts where the work started (microseconds before ``fn``
    # ran), not where the result left the window, tens of milliseconds
    # later; one straggler allowed for a preempted worker on a busy machine
    late = [started[i] - sp.t_mono for i, sp in enumerate(by_name["slow"])]
    assert all(gap >= 0.0 for gap in late), late
    assert sum(gap < 1e-3 for gap in late) >= 4, late
    # the consumer's waits lie on its own thread, in order, inside the
    # caller's span
    waits = by_name["wait"]
    assert {sp.thread for sp in waits} == {threading.current_thread().name}
    assert all(a.t_mono + a.duration_s <= b.t_mono
               for a, b in zip(waits, waits[1:]))
    assert caller.t_mono <= waits[0].t_mono
    if depth:
        assert {sp.thread for sp in by_name["read"]} == {"pipeline-source"}
    else:
        # no overlap: the consumer waits through the read and every stage
        for i, wait in enumerate(waits):
            assert wait.t_mono <= by_name["read"][i].t_mono
            h2d = by_name["h2d"][i]
            assert h2d.t_mono + h2d.duration_s <= (
                wait.t_mono + wait.duration_s)


def test_only_a_list_item_is_a_batch_of_rows():
    """A tuple is one record (the streaming histogram's chunk bounds, a
    training batch's arrays): its length counts fields, not rows."""
    from music_analyst_tpu.telemetry import configure

    tel = configure(enabled=True, directory=None)
    list(PrefetchPipeline([Stage("first", lambda x: x[0])], depth=1).run(
        zip(range(3), range(1, 4))))
    reads = [sp for sp in tel.spans if sp.name == "read"]
    assert [sp.attrs for sp in reads] == [
        {"pipeline": "pipeline", "seq": i} for i in range(3)
    ]
    assert all(sp.parent_id is None for sp in reads)


# ------------------------------------------------------------------- wire


def test_narrow_lengths_dtype_policy():
    values = np.array([0, 5, 127], dtype=np.int64)
    assert narrow_lengths(values, 128).dtype == np.int16
    assert narrow_lengths(values, (1 << 15) - 1).dtype == np.int16
    assert narrow_lengths(values, 1 << 15).dtype == np.int32
    np.testing.assert_array_equal(narrow_lengths(values, 128), values)


@pytest.mark.parametrize("length", [1, 7, 8, 9, 64, 100])
def test_pack_unpack_mask_roundtrip(length):
    rng = np.random.default_rng(3)
    mask = rng.integers(0, 2, size=(4, length)).astype(bool)
    packed = pack_mask(mask)
    assert packed.dtype == np.uint8
    assert packed.shape == (4, -(-length // 8))
    unpacked = np.asarray(unpack_mask(packed, length))
    np.testing.assert_array_equal(unpacked, mask)


def test_count_h2d_bytes_counters():
    from music_analyst_tpu.telemetry import configure

    tel = configure(enabled=True, directory=None)
    ids = np.zeros((4, 8), np.int16)
    lens = np.zeros((4,), np.int16)
    shipped = count_h2d_bytes([ids, lens])
    assert shipped == ids.nbytes + lens.nbytes
    assert tel.counters["pipeline.h2d_bytes"] == shipped
    # Baseline is the 4-byte wire both arrays used before narrowing.
    assert tel.counters["pipeline.h2d_bytes_saved"] == shipped


def test_forward_donation_disabled_on_cpu():
    from music_analyst_tpu.runtime.wire import forward_donation_kwargs

    assert forward_donation_kwargs(1, 2) == {}  # tests force JAX_PLATFORMS=cpu


# ---------------------------------------------------------------- backends


def test_distilbert_staged_hooks_match_classify_batch():
    from music_analyst_tpu.models.distilbert import (
        DistilBertClassifier,
        DistilBertConfig,
    )

    clf = DistilBertClassifier(config=DistilBertConfig.tiny(), max_len=32)
    texts = ["love and joy forever", "", "hate hate hate", "ok song"] * 3
    staged = clf.collect(clf.launch(clf.transfer(clf.prepare(texts))))
    assert staged == clf.classify_batch(texts)


def test_train_step_donates_state():
    import jax
    import jax.numpy as jnp

    from music_analyst_tpu.engines.train import (
        init_train_state,
        make_optimizer,
        make_train_step,
    )
    from music_analyst_tpu.models.llama import LlamaConfig, LlamaModel

    cfg = LlamaConfig.tiny()
    model = LlamaModel(cfg)
    ids = jnp.ones((2, 17), jnp.int32)
    lengths = jnp.full((2,), 17, jnp.int32)
    opt = make_optimizer()
    state = init_train_state(model, opt, (ids, lengths))
    step = make_train_step(model, opt)
    leaf_before = next(
        iter(jax.tree_util.tree_leaves(state.params))
    )
    new_state, loss = step(state, ids, lengths)
    assert np.isfinite(float(loss))
    # donate_argnums=(0,): the old state's buffers were handed to XLA.
    assert leaf_before.is_deleted()
    # The returned state is live and steps again.
    _, loss2 = step(new_state, ids, lengths)
    assert np.isfinite(float(loss2))


def test_prefetch_batches_places_and_narrows():
    import jax
    import jax.numpy as jnp

    from music_analyst_tpu.engines.train import prefetch_batches

    batches = [
        (np.ones((2, 16), np.int32), np.full((2,), 16, np.int64)),
        (np.ones((2, 16), np.int32), np.full((2,), 9, np.int64)),
    ]
    out = list(prefetch_batches(iter(batches), depth=2))
    assert len(out) == 2
    for token_ids, lengths in out:
        assert isinstance(token_ids, jax.Array)
        assert lengths.dtype == jnp.int16  # narrowed, widened in the loss
        np.testing.assert_array_equal(np.asarray(token_ids), 1)

    # Three-element batches keep their segment_ids (also narrowed).
    seg = np.array([[1] * 8 + [2] * 8] * 2, np.int64)
    out3 = list(
        prefetch_batches(
            iter([(np.ones((2, 16), np.int32), np.full((2,), 16), seg)]),
            depth=1,
        )
    )
    token_ids, lengths, seg_out = out3[0]
    assert seg_out.dtype == jnp.int16
    np.testing.assert_array_equal(np.asarray(seg_out), seg)


# ---------------------------------------------------------------- engines


def _read_artifacts(out_dir):
    out = {}
    for name in ("sentiment_totals.json", "sentiment_details.csv"):
        out[name] = (out_dir / name).read_bytes()
    return out


@pytest.mark.parametrize("depth", [0, 2])
def test_sentiment_artifacts_byte_identical_across_depths(
    fixture_csv, tmp_path, depth
):
    from music_analyst_tpu.engines.sentiment import run_sentiment

    out = tmp_path / f"d{depth}"
    run_sentiment(
        str(fixture_csv), mock=True, output_dir=str(out), quiet=True,
        batch_size=2, prefetch_depth=depth,
    )
    ref = tmp_path / "ref"
    run_sentiment(
        str(fixture_csv), mock=True, output_dir=str(ref), quiet=True,
        batch_size=2, prefetch_depth=0,
    )
    assert _read_artifacts(out) == _read_artifacts(ref)


def test_joint_word_counts_byte_identical_with_prefetch(
    fixture_csv, tmp_path
):
    from music_analyst_tpu.engines.joint import run_joint

    blobs = {}
    for depth in (0, 2):
        out = tmp_path / f"joint_d{depth}"
        run_joint(
            str(fixture_csv), output_dir=str(out), mock=True, quiet=True,
            batch_size=2, prefetch_depth=depth,
        )
        blobs[depth] = (out / "word_counts.csv").read_bytes()
    # SURVEY §5 golden contract: the ranking artifact cannot move by a
    # byte when the data plane pipelines.
    assert blobs[0] == blobs[2]


def test_sentiment_manifest_has_pipeline_section(fixture_csv, tmp_path):
    from music_analyst_tpu.engines.sentiment import run_sentiment

    run_sentiment(
        str(fixture_csv), mock=True, output_dir=str(tmp_path), quiet=True,
        batch_size=2, prefetch_depth=2,
    )
    manifest = json.loads((tmp_path / "run_manifest.json").read_text())
    pipeline = manifest["pipeline"]["pipeline"]
    assert pipeline["depth"] == 2
    stages = {s["stage"]: s for s in pipeline["stages"]}
    assert {"source", "tokenize", "h2d", "compute"} <= set(stages)
    for entry in stages.values():
        assert entry["stall_s"] >= 0.0
    assert pipeline["max_queue_depth"] >= 0
    assert manifest["gauges"]["pipeline.compute_stall_s"] >= 0.0


def test_sentiment_raising_backend_does_not_hang(fixture_csv, tmp_path):
    from music_analyst_tpu.engines.sentiment import run_sentiment

    class RaisingBackend:
        name = "raising"
        reports_latency = False

        def submit(self, texts):
            raise RuntimeError("tokenizer exploded")

        def collect(self, handle):
            return handle

    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match="tokenizer exploded"):
        run_sentiment(
            str(fixture_csv), output_dir=str(tmp_path), quiet=True,
            batch_size=2, backend=RaisingBackend(), prefetch_depth=2,
        )
    assert time.perf_counter() - t0 < 10.0


def test_tracing_shim_removed_and_unreferenced():
    """The PR-2 ``metrics/tracing.py`` deprecation shim is gone (PR 3
    migrated the last internal import; PR 4 deleted it) — and nothing in
    the package source refers to it anymore."""
    import pathlib

    import music_analyst_tpu

    pkg_root = pathlib.Path(music_analyst_tpu.__file__).parent
    assert not (pkg_root / "metrics" / "tracing.py").exists()
    with pytest.raises(ImportError):
        import music_analyst_tpu.metrics.tracing  # noqa: F401
    offenders = [
        str(path)
        for path in pkg_root.rglob("*.py")
        if "metrics.tracing" in path.read_text(encoding="utf-8")
    ]
    assert not offenders, f"stale metrics.tracing imports: {offenders}"
