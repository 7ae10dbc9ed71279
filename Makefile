# Top-level convenience targets.  The native library has its own Makefile
# (make -C native); tests force the CPU platform via tests/conftest.py.

PY ?= python

.PHONY: smoke test native

# Router self-check body (exported below; the smoke recipe runs it with
# $(PY) -c "$$ROUTER_SELFCHECK" <telemetry-dir>): start 2 mock replica
# workers behind the ReplicaRouter, SIGKILL one mid-load, and assert
# every admitted request is answered or structurally shed AND the run
# manifest's serving.router section records the health transition.
define ROUTER_SELFCHECK
import json, os, signal, sys, tempfile
from music_analyst_tpu.telemetry import configure, get_telemetry
from music_analyst_tpu.serving.router import ReplicaRouter, spawn_replicas
from music_analyst_tpu.serving.server import SentimentServer

out = sys.argv[1]
configure(enabled=True, directory=out)
tel = get_telemetry()
with tel.run_scope("serve", None):
    with tempfile.TemporaryDirectory() as base:
        handles = spawn_replicas(2, base, model="mock", mock=True,
                                 warmup=False)
        router = ReplicaRouter(handles, poll_interval_s=0.1).start()
        server = SentimentServer(router, mode="unix", router=router)
        reqs = [router.submit(i, "sentiment", "happy %d" % i)
                for i in range(4)]
        os.kill(handles[0].proc.pid, signal.SIGKILL)
        reqs += [router.submit(4 + i, "sentiment", "gray %d" % i)
                 for i in range(4)]
        for r in reqs:
            assert r.wait(60), "request %s never settled" % r.id
        ok = sum(1 for r in reqs if r.response.get("ok"))
        shed = sum(1 for r in reqs if not r.response.get("ok")
                   and r.response["error"]["kind"] in
                   ("queue_full", "replica_lost", "draining"))
        assert ok + shed == len(reqs), [r.response for r in reqs]
        stats = router.stats()
        assert stats["health_transitions"], "no health transition"
        router.drain()
manifest = json.load(open(os.path.join(out, "run_manifest.json")))
rt = manifest["serving"]["router"]
assert rt["health_transitions"], rt
assert rt["requeued"] >= 0 and rt["replica_count"] == 2, rt
print("router self-check ok:", ok, "answered,", shed, "shed,",
      rt["requeued"], "requeued,",
      len(rt["health_transitions"]), "health transition(s)")
endef
export ROUTER_SELFCHECK

# Crash-recovery self-check body (exported below): the mid-decode drill
# from benchmarks/crash.py — SIGKILL a journaled generate server while a
# request is in flight on device, restart it on the SAME journal dir,
# re-send everything a reconnecting client would retry, and assert 100%
# accounting, zero duplicate computes (the journal's dedup index answers
# already-sent replies byte-identically), and the unclean_shutdown stamp
# in the restart's run manifest.
define CRASH_SELFCHECK
import sys
from benchmarks.crash import _GEN_ARGS, _gen_trace, run_drill
row = run_drill("mid_decode", "decode.step:crash@3", sys.argv[1],
                model_args=_GEN_ARGS, trace=_gen_trace(3, seed=17))
assert row["killed_by_sigkill"], row
assert row["recovered_exit_ok"], row
assert row["all_accounted"] and row["loadgen_silent_drops"] == 0, row
assert row["duplicates_deduped"], row
assert row["unclean_stamped"], row
print("crash-recovery self-check ok:",
      row["journal"]["replayed"], "replayed,",
      row["journal"]["deduped"], "deduped,",
      "%.1fs" % row["wall_s"])
endef
export CRASH_SELFCHECK

# Burn-rate self-check body (exported below; run with $(PY) -c
# "$$BURN_SELFCHECK" <burst-dir> <steady-dir>): the burst run's
# metrics.jsonl must hold EXACTLY ONE firing burn-rate alert — the bulk
# tenant's shed burn — whose trace_id resolves to a kept shed exemplar
# in the same run's request_traces.jsonl; the steady run must sample
# but stay silent.
define BURN_SELFCHECK
import json, sys
burst, steady = sys.argv[1], sys.argv[2]

def load(path):
    recs = [json.loads(l) for l in open(path) if l.strip()]
    return ([r for r in recs if r.get("type") == "sample"],
            [r for r in recs if r.get("type") == "alert"])

samples, alerts = load(burst + "/metrics.jsonl")
assert len(samples) >= 2, f"burst run took {len(samples)} sample(s)"
firing = [a for a in alerts if a["state"] == "firing"]
assert len(firing) == 1, [a.get("alert") for a in firing]
alert = firing[0]
assert alert["alert"] == "shed_burn_rate", alert
assert alert["tenant"] == "bulk", alert
assert alert["burn_fast"] >= alert["threshold"], alert
tid = alert.get("trace_id")
assert isinstance(tid, str) and tid, f"alert carries no trace_id: {alert}"
traces = [json.loads(l)
          for l in open(burst + "/request_traces.jsonl") if l.strip()]
assert any(t.get("trace_id") == tid for t in traces), \
    f"alert trace_id {tid} not kept in request_traces.jsonl"
s_samples, s_alerts = load(steady + "/metrics.jsonl")
assert len(s_samples) >= 2, f"steady run took {len(s_samples)} sample(s)"
s_firing = [a for a in s_alerts if a["state"] == "firing"]
assert not s_firing, s_firing
print("burn-rate self-check ok: shed_burn_rate tenant=bulk,",
      "burn %.0fx/%.0fx," % (alert["burn_fast"], alert["burn_slow"]),
      "trace", tid, "kept, steady run silent")
endef
export BURN_SELFCHECK

# Engine-ledger self-check body (exported below; run with $(PY) -c
# "$$LEDGER_SELFCHECK" <replies.ndjson> <profile-dir>): after a stdio
# generate burst over two tenants, every reply must be ok, the stats op
# must surface the live ledger, and the final cumulative record in
# engine_ledger.jsonl must tile — classified seconds covering >=95% of
# the engine wall and per-tenant chip-seconds summing to the wall within
# 2% — with zero flush drops and no torn line.
define LEDGER_SELFCHECK
import json, os, sys
replies_path, profile_dir = sys.argv[1], sys.argv[2]
replies = [json.loads(l) for l in open(replies_path) if l.strip()]
by_id = {r["id"]: r for r in replies}
gen = [r for r in replies if r["id"] != "end"]
assert gen and all(r.get("ok") for r in gen), \
    [r for r in gen if not r.get("ok")]
live = ((by_id["end"].get("stats") or {}).get("decode") or {}).get(
    "ledger") or {}
assert live.get("ticks", 0) > 0, f"stats op carries no live ledger: {live}"
path = os.path.join(profile_dir, "engine_ledger.jsonl")
raw = open(path, "rb").read()
assert raw.endswith(b"\n"), "torn final line in engine_ledger.jsonl"
recs = [json.loads(l) for l in raw.decode("utf-8").splitlines()
        if l.strip()]
assert recs and all(r.get("type") == "ledger" for r in recs), recs[:2]
final = recs[-1]["ledger"]
wall = final["engine_wall_s"]
assert wall > 0.0, final
covered = sum(final["seconds"].values())
assert covered >= 0.95 * wall, (covered, wall)
chip = sum(final["chip_seconds"].values())
assert abs(chip - wall) <= 0.02 * wall, (chip, wall)
assert final["ledger_drops"] == 0, final
print("engine-ledger self-check ok:",
      f"{len(recs)} flush(es), coverage {covered / wall:.3f},",
      "chip-seconds within",
      f"{abs(chip - wall) / max(wall, 1e-9) * 100.0:.2f}% of wall")
endef
export LEDGER_SELFCHECK

# Paged-attention kernel self-check body (exported below; run with
# $(PY) -c "$$KERNEL_SELFCHECK"): random pool/table/mask with odd valid
# lengths and a trash-page table row, both Pallas bodies (exact batched
# and the page-streaming TPU body) run in interpret mode against the
# naive f32 gather oracle, then the int8 path.
define KERNEL_SELFCHECK
import numpy as np
import jax.numpy as jnp
from music_analyst_tpu.ops.paged_attention import (
    paged_attention, paged_attention_reference)
from music_analyst_tpu.ops.quant import quantize_kv_page
rng = np.random.RandomState(0)
P, pps, n, n_kv, H, D = 8, 4, 3, 2, 4, 8
n_pages = n * pps
table = rng.permutation(n_pages).reshape(n, pps).astype(np.int32)
table[0, -1] = n_pages  # trash page
lengths = np.array([13, 7, 21], np.int32)  # odd, off the page grid
mask = jnp.asarray(np.arange(pps * P)[None, :] < lengths[:, None])
shape = (n_pages + 1, P, n_kv, D)
k = jnp.asarray(rng.standard_normal(shape), dtype=jnp.bfloat16)
v = jnp.asarray(rng.standard_normal(shape), dtype=jnp.bfloat16)
q = jnp.asarray(rng.standard_normal((n, 1, H, D)), dtype=jnp.bfloat16)
t = jnp.asarray(table)
ref = np.asarray(paged_attention_reference(q, k, v, t, mask))
for stream in (False, True):
    out = np.asarray(paged_attention(
        q, k, v, t, mask, interpret=True, stream=stream), np.float32)
    assert np.allclose(out, ref, atol=0.06, rtol=0.06), \
        f"stream={stream} body diverged from the f32 oracle"
kq, ks = quantize_kv_page(k.astype(jnp.float32))
vq, vs = quantize_kv_page(v.astype(jnp.float32))
out8 = np.asarray(paged_attention(
    q, kq, vq, t, mask, key_scale=ks, value_scale=vs,
    interpret=True), np.float32)
assert np.allclose(out8, ref, atol=0.15), "int8 path diverged"
print("paged-attention kernel self-check ok:",
      "exact+stream+int8 vs oracle at P=8, odd lengths, trash row")
endef
export KERNEL_SELFCHECK

# Fast observability gate: profiling + telemetry + pipeline +
# observability + corpus-cache/streaming unit tests, then one
# smoke-shaped bench.py run (one process, --baseline), asserting the
# ONE-JSON-line stdout contract the round driver depends on, a
# two-invocation warm-corpus-cache self-check (second analyze of the same file must hit the cache AND write a
# byte-identical word_counts.csv), and finally profile-diff +
# telemetry-report self-checks over two smoke bench lines.  Runs in a
# few minutes on the sandboxed CPU.
smoke:
	env JAX_PLATFORMS=cpu \
		$(PY) -m pytest tests/test_profiling.py tests/test_telemetry.py \
		tests/test_telemetry_contract.py tests/test_runtime_pipeline.py \
		tests/test_observability.py tests/test_corpus_cache.py \
		tests/test_wq_store.py tests/test_serving.py \
		tests/test_resilience.py tests/test_continuous.py \
		tests/test_kv_pages.py tests/test_paged_attention.py \
		tests/test_router.py \
		tests/test_journal.py tests/test_speculative.py \
		tests/test_reqtrace.py tests/test_metrics_plane.py \
		tests/test_engine_ledger.py tests/test_fault_coverage.py \
		tests/test_response_cache.py -q
	# paged-attention kernel self-check (body in KERNEL_SELFCHECK above):
	# both interpret-mode kernel bodies + the int8 path vs the f32 oracle.
	env JAX_PLATFORMS=cpu \
		$(PY) -c "$$KERNEL_SELFCHECK" || \
		{ echo "paged-attention kernel self-check failed"; exit 1; }
	env JAX_PLATFORMS=cpu MUSICAAL_BENCH_SMOKE=1 \
		$(PY) bench.py --baseline \
		| $(PY) -c "import json,sys; \
lines=[l for l in sys.stdin.read().splitlines() if l.strip()]; \
assert len(lines)==1, f'expected ONE JSON line, got {len(lines)}'; \
payload=json.loads(lines[0]); \
assert 'vs_baseline_detail' in payload, 'missing --baseline detail'; \
print('smoke ok:', payload['metric'], payload['value'])"
	# corpus-cache warm self-check: analyze the same fixture twice with
	# the cache pointed at a fresh dir — the second run must record a
	# cache hit in its run manifest and write a byte-identical
	# word_counts.csv (golden contract: the cache may never change
	# output bytes).
	cachetmp=$$(mktemp -d) && trap 'rm -rf "$$cachetmp"' EXIT && \
	for run in cold warm; do \
		env JAX_PLATFORMS=cpu \
			MUSICAAL_CORPUS_CACHE="$$cachetmp/cache" \
			$(PY) -m music_analyst_tpu analyze tests/fixtures/mini_songs.csv \
			--output-dir "$$cachetmp/$$run" --no-split >/dev/null || \
			{ echo "corpus-cache $$run run failed"; exit 1; }; \
	done && \
	cmp "$$cachetmp/cold/word_counts.csv" "$$cachetmp/warm/word_counts.csv" || \
		{ echo "warm-cache word_counts.csv diverged from cold"; exit 1; }; \
	grep -q '"hits": [1-9]' "$$cachetmp/warm/run_manifest.json" || \
		{ echo "warm run did not hit the corpus cache"; exit 1; }; \
	echo "corpus-cache warm self-check ok"
	# profile-diff self-check: two smoke bench lines must both satisfy
	# the one-line contract and feed the regression gate without an
	# exit-2 (unusable input).  Exit 1 (regression verdict) is tolerated
	# — smoke shapes on a 1-core sandbox are too noisy to gate on.
	tmpdir=$$(mktemp -d) && trap 'rm -rf "$$tmpdir"' EXIT && \
	for side in a b; do \
		env JAX_PLATFORMS=cpu MUSICAAL_BENCH_SMOKE=1 \
			$(PY) bench.py \
			> "$$tmpdir/$$side.json" || exit 1; \
		test "$$(grep -c . "$$tmpdir/$$side.json")" = 1 || \
			{ echo "bench $$side: not ONE JSON line"; exit 1; }; \
	done && \
	env JAX_PLATFORMS=cpu \
		$(PY) -m music_analyst_tpu profile-diff \
		"$$tmpdir/a.json" "$$tmpdir/b.json" --threshold 0.5; rc=$$?; \
	if [ $$rc -eq 2 ]; then echo "profile-diff: unusable input"; exit 1; \
	else echo "profile-diff self-check ok (exit $$rc)"; fi; \
	env JAX_PLATFORMS=cpu \
		$(PY) -m music_analyst_tpu telemetry-report \
		"$$tmpdir/a.json" "$$tmpdir/b.json" || \
		{ echo "telemetry-report self-check failed"; exit 1; }; \
	echo "telemetry-report self-check ok"
	# serving self-check: start the stdio server, send 3 requests, and
	# assert the replies come back in order with the right ids AND that
	# the run manifest grew a `serving` section (warm residency + batcher
	# stats are a manifest contract, not just a wire one).
	servetmp=$$(mktemp -d) && trap 'rm -rf "$$servetmp"' EXIT && \
	printf '%s\n' \
		'{"id":"s1","op":"sentiment","text":"I love this happy day"}' \
		'{"id":"s2","op":"wordcount","text":"hello hello world"}' \
		'{"id":"s3","op":"ping"}' | \
	env JAX_PLATFORMS=cpu \
		$(PY) -m music_analyst_tpu serve --stdio --mock --quiet \
		--max-batch 2 --max-wait-ms 2 --telemetry-dir "$$servetmp" \
		> "$$servetmp/replies.ndjson" || { echo "serve run failed"; exit 1; }; \
	$(PY) -c "import json,sys; \
	lines=[json.loads(l) for l in open(sys.argv[1]) if l.strip()]; \
	assert [r['id'] for r in lines]==['s1','s2','s3'], [r['id'] for r in lines]; \
	assert all(r['ok'] for r in lines), lines; \
	manifest=json.load(open(sys.argv[2])); \
	serving=manifest['serving']; \
	assert serving['requests']['completed']==2, serving['requests']; \
	assert serving['residency']['warm'] is True, serving['residency']; \
	print('serving self-check ok:', serving['requests']['batches'], 'batch(es)')" \
		"$$servetmp/replies.ndjson" "$$servetmp/run_manifest.json" || \
		{ echo "serving self-check failed"; exit 1; }
	# response-cache self-check: the same sentiment request through two
	# serve processes sharing one cache dir — the warm process must
	# answer from the disk tier (stats: hits==1, ZERO batches dispatched,
	# the hit never reaches the device) with a reply byte-identical to
	# the cold one (the cache may never change output bytes; the `cached`
	# stamp lives in stats/trace, never the payload).
	rctmp=$$(mktemp -d) && trap 'rm -rf "$$rctmp"' EXIT && \
	for run in cold warm; do \
		printf '%s\n' \
			'{"id":"c1","op":"sentiment","text":"I love this happy day"}' \
			'{"id":"c2","op":"stats"}' | \
		env JAX_PLATFORMS=cpu \
			$(PY) -m music_analyst_tpu serve --stdio --mock --quiet \
			--max-batch 2 --max-wait-ms 2 \
			--response-cache-dir "$$rctmp/rcache" \
			> "$$rctmp/$$run.ndjson" || \
			{ echo "response-cache $$run run failed"; exit 1; }; \
	done && \
	$(PY) -c "import json,sys; \
	cold=[json.loads(l) for l in open(sys.argv[1]) if l.strip()]; \
	warm=[json.loads(l) for l in open(sys.argv[2]) if l.strip()]; \
	sans=lambda r: {k:v for k,v in r.items() if k!='id'}; \
	assert sans(warm[0])==sans(cold[0]), 'cached reply diverged from computed'; \
	assert 'cached' not in warm[0], warm[0]; \
	rc=warm[1]['stats']['response_cache']; \
	assert rc['hits']==1 and rc['disk_hits']==1, rc; \
	reqs=warm[1]['stats']['requests']; \
	assert reqs['batches']==0 and reqs['rows']==0, reqs; \
	print('response-cache self-check ok: 1 disk hit, 0 dispatches')" \
		"$$rctmp/cold.ndjson" "$$rctmp/warm.ndjson" || \
		{ echo "response-cache self-check failed"; exit 1; }
	# generate-interleave self-check: one continuous-decode generate
	# request sandwiched between two sentiment requests on the same
	# stdio stream — replies must come back in order, the generate reply
	# must carry text/label/tokens from the slot runtime, and the
	# manifest's serving section must grow a `decode` block.
	gentmp=$$(mktemp -d) && trap 'rm -rf "$$gentmp"' EXIT && \
	printf '%s\n' \
		'{"id":"g1","op":"sentiment","text":"I love this happy day"}' \
		'{"id":"g2","op":"generate","text":"sunny morning","max_new_tokens":4}' \
		'{"id":"g3","op":"sentiment","text":"sad and gray"}' | \
	env JAX_PLATFORMS=cpu \
		$(PY) -m music_analyst_tpu serve --stdio --model llama-tiny --quiet \
		--slots 2 --prefill-chunk 32 --max-new-tokens 4 \
		--max-batch 2 --max-wait-ms 2 --telemetry-dir "$$gentmp" \
		> "$$gentmp/replies.ndjson" || { echo "generate serve run failed"; exit 1; }; \
	$(PY) -c "import json,sys; \
	lines=[json.loads(l) for l in open(sys.argv[1]) if l.strip()]; \
	assert [r['id'] for r in lines]==['g1','g2','g3'], [r['id'] for r in lines]; \
	assert all(r['ok'] for r in lines), lines; \
	gen=lines[1]; \
	assert gen['op']=='generate' and 'text' in gen and 'label' in gen, gen; \
	manifest=json.load(open(sys.argv[2])); \
	decode=manifest['serving']['decode']; \
	assert decode['completed']==1, decode; \
	print('generate-interleave self-check ok:', decode['tokens_generated'], 'token(s)')" \
		"$$gentmp/replies.ndjson" "$$gentmp/run_manifest.json" || \
		{ echo "generate-interleave self-check failed"; exit 1; }
	# prefix-cache self-check: the same generate prompt three times on one
	# stdio stream — with 2 slots the third request must wait for a slot,
	# so it admits after a completed prefill seeded the radix tree: the
	# manifest's decode block must report prefix_cache hits >= 1 while the
	# replies stay identical (sharing may never change output bytes).
	pctmp=$$(mktemp -d) && trap 'rm -rf "$$pctmp"' EXIT && \
	printf '%s\n' \
		'{"id":"p1","op":"generate","text":"sunny morning","max_new_tokens":4}' \
		'{"id":"p2","op":"generate","text":"sunny morning","max_new_tokens":4}' \
		'{"id":"p3","op":"generate","text":"sunny morning","max_new_tokens":4}' | \
	env JAX_PLATFORMS=cpu \
		$(PY) -m music_analyst_tpu serve --stdio --model llama-tiny --quiet \
		--slots 2 --prefill-chunk 32 --max-new-tokens 4 --page-size 16 \
		--max-batch 2 --max-wait-ms 2 --telemetry-dir "$$pctmp" \
		> "$$pctmp/replies.ndjson" || { echo "prefix-cache serve run failed"; exit 1; }; \
	$(PY) -c "import json,sys; \
	lines=[json.loads(l) for l in open(sys.argv[1]) if l.strip()]; \
	assert [r['id'] for r in lines]==['p1','p2','p3'], [r['id'] for r in lines]; \
	assert all(r['ok'] for r in lines), lines; \
	texts={r['text'] for r in lines}; \
	assert len(texts)==1, f'identical prompts diverged: {texts}'; \
	decode=json.load(open(sys.argv[2]))['serving']['decode']; \
	assert decode['kv_backend']=='paged', decode['kv_backend']; \
	pc=decode['prefix_cache']; \
	assert pc['hits']>=1, pc; \
	print('prefix-cache self-check ok:', pc['hits'], 'hit(s),', \
	      pc['tokens_shared'], 'token(s) shared')" \
		"$$pctmp/replies.ndjson" "$$pctmp/run_manifest.json" || \
		{ echo "prefix-cache self-check failed"; exit 1; }
	# speculation self-check: one long repetitive generate prompt through
	# the stdio server with and without draft-and-verify (--speculate-k)
	# — the replies must be byte-identical (speculation may never change
	# output bytes), and the speculative run's manifest must show verify
	# dispatches that netted more than one committed token each once the
	# stream entered its cycle (the whole point of drafting).
	spectmp=$$(mktemp -d) && trap 'rm -rf "$$spectmp"' EXIT && \
	for arm in plain spec; do \
		if [ $$arm = spec ]; then sk=4; else sk=0; fi; \
		printf '%s\n' \
			'{"id":"k1","op":"generate","text":"la la la la la la","max_new_tokens":96}' | \
		env JAX_PLATFORMS=cpu \
			$(PY) -m music_analyst_tpu serve --stdio --model llama-tiny --quiet \
			--slots 2 --prefill-chunk 32 --max-new-tokens 96 --speculate-k $$sk \
			--max-batch 2 --max-wait-ms 2 --telemetry-dir "$$spectmp/$$arm" \
			> "$$spectmp/$$arm.ndjson" || \
			{ echo "speculation $$arm run failed"; exit 1; }; \
	done && \
	$(PY) -c "import json,sys; \
	plain=[json.loads(l) for l in open(sys.argv[1]) if l.strip()]; \
	spec=[json.loads(l) for l in open(sys.argv[2]) if l.strip()]; \
	assert [r['text'] for r in plain]==[r['text'] for r in spec], \
	    'speculation changed output bytes'; \
	sp=json.load(open(sys.argv[3]))['serving']['decode']['speculation']; \
	assert sp['enabled'] and sp['k']==4, sp; \
	assert sp['dispatches']>=1 and sp['fallbacks']==0, sp; \
	assert sp['accepted_tokens_per_dispatch']>1.0, sp; \
	print('speculation self-check ok:', sp['dispatches'], 'dispatch(es),', \
	      sp['accepted_tokens_per_dispatch'], 'tok/dispatch,', \
	      sp['acceptance_rate'], 'acceptance')" \
		"$$spectmp/plain.ndjson" "$$spectmp/spec.ndjson" \
		"$$spectmp/spec/run_manifest.json" || \
		{ echo "speculation self-check failed"; exit 1; }
	# router self-check (body in ROUTER_SELFCHECK above): 2 replicas,
	# 8 requests, SIGKILL one mid-load — zero admitted requests lost,
	# health transition in the manifest's serving.router section.
	routertmp=$$(mktemp -d) && trap 'rm -rf "$$routertmp"' EXIT && \
	env JAX_PLATFORMS=cpu \
		$(PY) -c "$$ROUTER_SELFCHECK" "$$routertmp" || \
		{ echo "router self-check failed"; exit 1; }
	# crash-recovery self-check (body in CRASH_SELFCHECK above): SIGKILL
	# the journaled generate server mid-decode, restart on the same
	# journal dir — every request answered, nothing computed twice,
	# unclean shutdown stamped.
	crashtmp=$$(mktemp -d) && trap 'rm -rf "$$crashtmp"' EXIT && \
	env JAX_PLATFORMS=cpu \
		$(PY) -c "$$CRASH_SELFCHECK" "$$crashtmp" || \
		{ echo "crash-recovery self-check failed"; exit 1; }
	# chaos self-check: analyze with a transient fault injected at the
	# ingest seam — the run must recover (retry counter in the manifest)
	# and write a word_counts.csv byte-identical to the clean run (the
	# golden contracts hold under injected failure).
	chaostmp=$$(mktemp -d) && trap 'rm -rf "$$chaostmp"' EXIT && \
	env JAX_PLATFORMS=cpu \
		$(PY) -m music_analyst_tpu analyze tests/fixtures/mini_songs.csv \
		--output-dir "$$chaostmp/clean" --no-split >/dev/null || \
		{ echo "chaos clean run failed"; exit 1; }; \
	env JAX_PLATFORMS=cpu \
		MUSICAAL_FAULTS="ingest.read:error@1" \
		$(PY) -m music_analyst_tpu analyze tests/fixtures/mini_songs.csv \
		--output-dir "$$chaostmp/faulted" --no-split >/dev/null || \
		{ echo "chaos injected run failed (retry did not recover)"; exit 1; }; \
	cmp "$$chaostmp/clean/word_counts.csv" "$$chaostmp/faulted/word_counts.csv" || \
		{ echo "injected-fault word_counts.csv diverged from clean"; exit 1; }; \
	grep -q '"retry.ingest.read"' "$$chaostmp/faulted/run_manifest.json" || \
		{ echo "injected run manifest lacks the retry counter"; exit 1; }; \
	echo "chaos injected-fault self-check ok"
	# trace self-check: one traced generate request under --trace-sample
	# 1.0 — request_traces.jsonl must hold its waterfall with >=6 phases
	# whose span sum covers >=95% of the request's measured wire latency,
	# and trace-report must reconstruct a complete waterfall (exit 0).
	tracetmp=$$(mktemp -d) && trap 'rm -rf "$$tracetmp"' EXIT && \
	printf '%s\n' \
		'{"id":"t1","op":"generate","text":"sunny morning","max_new_tokens":4}' | \
	env JAX_PLATFORMS=cpu \
		$(PY) -m music_analyst_tpu serve --stdio --model llama-tiny --quiet \
		--slots 2 --prefill-chunk 32 --max-new-tokens 4 \
		--max-batch 2 --max-wait-ms 2 --trace-sample 1.0 \
		--profile-dir "$$tracetmp" --telemetry-dir "$$tracetmp" \
		> "$$tracetmp/replies.ndjson" || { echo "traced serve run failed"; exit 1; }; \
	$(PY) -c "import json,sys; \
	lines=[json.loads(l) for l in open(sys.argv[1]) if l.strip()]; \
	assert lines and lines[0]['ok'] and 'trace_id' in lines[0], lines; \
	recs=[json.loads(l) for l in open(sys.argv[2]) if l.strip()]; \
	rec=[r for r in recs if r['trace_id']==lines[0]['trace_id']][0]; \
	phases=[s for s in rec['spans'] if s['cat']=='phase']; \
	assert len(phases)>=6, [s['name'] for s in phases]; \
	cover=sum(s['dur'] for s in phases); \
	assert cover >= 0.95*rec['wire_s'], (cover, rec['wire_s']); \
	print('trace self-check ok:', len(phases), 'phases,', \
	      round(100.0*cover/rec['wire_s'],1), 'pct coverage')" \
		"$$tracetmp/replies.ndjson" "$$tracetmp/request_traces.jsonl" || \
		{ echo "trace self-check failed"; exit 1; }; \
	env JAX_PLATFORMS=cpu \
		$(PY) -m music_analyst_tpu trace-report "$$tracetmp" >/dev/null || \
		{ echo "trace-report self-check failed"; exit 1; }; \
	echo "trace-report self-check ok"
	# overload self-check: burst one stdio stream past a 1 req/s bulk
	# tenant budget while a single high-priority gold request rides along
	# — gold must be answered ok inside its (generous) TTFT SLO, every
	# bulk shed must be structured (queue_full/slo_unattainable with a
	# numeric retry_after_ms), and the stats op's slo section must show
	# the sheds charged to the bulk tenant only (per-tenant isolation).
	overtmp=$$(mktemp -d) && trap 'rm -rf "$$overtmp"' EXIT && \
	{ for i in 0 1 2 3 4 5 6 7 8 9; do \
		printf '{"id":"b%s","op":"sentiment","text":"bulk row %s","tenant":"bulk","priority":1}\n' "$$i" "$$i"; \
	done; \
	printf '%s\n' \
		'{"id":"gold","op":"sentiment","text":"I love this happy day","tenant":"gold","priority":5}' \
		'{"id":"end","op":"stats"}'; } | \
	env JAX_PLATFORMS=cpu \
		$(PY) -m music_analyst_tpu serve --stdio --mock --quiet \
		--max-batch 4 --max-wait-ms 2 --max-queue 8 \
		--tenant-budget 1 --ttft-slo-ms 5000 \
		> "$$overtmp/replies.ndjson" || { echo "overload serve run failed"; exit 1; }; \
	$(PY) -c "import json,sys; \
	lines=[json.loads(l) for l in open(sys.argv[1]) if l.strip()]; \
	assert len(lines)==12, f'expected 12 replies, got {len(lines)}'; \
	by_id={r['id']: r for r in lines}; \
	assert by_id['gold']['ok'], by_id['gold']; \
	sheds=[r for r in lines if not r.get('ok') and r['id']!='end']; \
	assert sheds, 'burst past the tenant budget shed nothing'; \
	assert all(r['error']['kind'] in ('queue_full','slo_unattainable') \
	           and r['error'].get('retry_after_ms', 0) >= 1.0 \
	           for r in sheds), sheds; \
	slo=by_id['end']['stats']['slo']; \
	assert slo['tenants']['bulk']['shed'] >= 1, slo; \
	assert slo['tenants']['gold']['shed'] == 0, slo; \
	print('overload self-check ok:', by_id['gold']['label'], 'gold,', \
	      len(sheds), 'structured shed(s)')" \
		"$$overtmp/replies.ndjson" || \
		{ echo "overload self-check failed"; exit 1; }
	# burn-rate self-check (body in BURN_SELFCHECK above): the overload
	# burst replayed through a journaled, metered stdio server — the bulk
	# flood past its 1 req/s budget must fire exactly one burn-rate alert
	# whose trace_id resolves to a kept shed exemplar; a within-budget
	# steady run on the same flags must sample but fire zero.  The 200ms
	# interval makes the sample set deterministic (baseline + close-time
	# final, after every reply and kept trace has flushed).
	burntmp=$$(mktemp -d) && trap 'rm -rf "$$burntmp"' EXIT && \
	{ for i in 0 1 2 3 4 5 6 7 8 9; do \
		printf '{"id":"b%s","op":"sentiment","text":"bulk row %s","tenant":"bulk","priority":1}\n' "$$i" "$$i"; \
	done; \
	printf '%s\n' \
		'{"id":"gold","op":"sentiment","text":"I love this happy day","tenant":"gold","priority":5}'; } | \
	env JAX_PLATFORMS=cpu \
		$(PY) -m music_analyst_tpu serve --stdio --mock --quiet \
		--max-batch 4 --max-wait-ms 2 --max-queue 8 \
		--tenant-budget 1 --ttft-slo-ms 5000 \
		--journal-dir "$$burntmp/journal" --trace-sample 0 \
		--metrics-interval-ms 200 --profile-dir "$$burntmp/burst" \
		> "$$burntmp/burst.ndjson" || { echo "burn-rate burst run failed"; exit 1; }; \
	printf '%s\n' \
		'{"id":"c1","op":"sentiment","text":"calm seas","tenant":"bulk","priority":1}' \
		'{"id":"c2","op":"sentiment","text":"steady light","tenant":"gold","priority":5}' | \
	env JAX_PLATFORMS=cpu \
		$(PY) -m music_analyst_tpu serve --stdio --mock --quiet \
		--max-batch 4 --max-wait-ms 2 --max-queue 8 \
		--tenant-budget 1 --ttft-slo-ms 5000 \
		--journal-dir "$$burntmp/journal2" --trace-sample 0 \
		--metrics-interval-ms 200 --profile-dir "$$burntmp/steady" \
		> "$$burntmp/steady.ndjson" || { echo "burn-rate steady run failed"; exit 1; }; \
	$(PY) -c "$$BURN_SELFCHECK" "$$burntmp/burst" "$$burntmp/steady" || \
		{ echo "burn-rate self-check failed"; exit 1; }
	# live-monitor self-check: serve on a unix socket in the background,
	# wait for the socket to appear, and assert the jax-free
	# `monitor --once` renders a healthy snapshot (exit 0) against the
	# live front end.
	montmp=$$(mktemp -d) && trap 'rm -rf "$$montmp"' EXIT && \
	env JAX_PLATFORMS=cpu \
		$(PY) -m music_analyst_tpu serve --socket "$$montmp/sock" \
		--mock --quiet --max-batch 4 --max-wait-ms 2 \
		--metrics-interval-ms 200 --profile-dir "$$montmp" & \
	srvpid=$$!; \
	tries=0; \
	while [ ! -S "$$montmp/sock" ] && [ $$tries -lt 100 ]; do \
		sleep 0.1; tries=$$((tries + 1)); \
	done; \
	[ -S "$$montmp/sock" ] || { kill $$srvpid 2>/dev/null; \
		echo "monitor self-check: socket never appeared"; exit 1; }; \
	env JAX_PLATFORMS=cpu \
		$(PY) -m music_analyst_tpu monitor --once --socket "$$montmp/sock" || \
		{ kill $$srvpid 2>/dev/null; echo "monitor self-check failed"; exit 1; }; \
	kill $$srvpid 2>/dev/null; wait $$srvpid 2>/dev/null; \
	echo "monitor self-check ok"
	# engine-ledger self-check (body in LEDGER_SELFCHECK above): a stdio
	# generate burst over two tenants on the continuous scheduler, ledger
	# flushing on a 100ms cadence to the profile dir — the goodput
	# accounting must tile (coverage >= 0.95, chip-seconds within 2% of
	# the engine wall) and the JSONL must land intact.
	ledgertmp=$$(mktemp -d) && trap 'rm -rf "$$ledgertmp"' EXIT && \
	{ for i in 0 1 2 3 4 5; do \
		case $$(( i % 2 )) in 0) t=gold;; *) t=bulk;; esac; \
		printf '{"id":"g%s","op":"generate","text":"verse %s of the burst","tenant":"%s","max_new_tokens":4}\n' "$$i" "$$i" "$$t"; \
	done; \
	printf '%s\n' '{"id":"end","op":"stats"}'; } | \
	env JAX_PLATFORMS=cpu \
		MUSICAAL_LEDGER_INTERVAL_MS=100 \
		$(PY) -m music_analyst_tpu serve --stdio --model llama-tiny --quiet \
		--slots 2 --prefill-chunk 32 --max-new-tokens 4 \
		--max-batch 2 --max-wait-ms 2 --profile-dir "$$ledgertmp" \
		> "$$ledgertmp/replies.ndjson" || \
		{ echo "engine-ledger serve run failed"; exit 1; }; \
	$(PY) -c "$$LEDGER_SELFCHECK" "$$ledgertmp/replies.ndjson" "$$ledgertmp" || \
		{ echo "engine-ledger self-check failed"; exit 1; }

test:
	$(PY) -m pytest tests/ -q

native:
	$(MAKE) -C native
