"""Decode throughput: single-scan generation vs per-token host stepping.

Backs the "Generation" section in PERFORMANCE.md.  Every per-token host
step pays a host↔device round-trip (its cost: not measured on this
host), so the framework decodes a whole batch inside one jitted
``lax.scan`` (``models/llama.py:generate_batch``); the per-token
``generate`` loop is kept as the differential oracle.  This suite
measures both — the loop on a deliberately tiny budget, because that IS
the result being demonstrated.
"""

from __future__ import annotations

from benchmarks import suite
from benchmarks._util import device_info, smoke, timed


@suite("generation")
def run() -> dict:
    import time

    from music_analyst_tpu.models.llama import (
        LlamaConfig,
        LlamaZeroShotClassifier,
    )

    n_prompts = 8 if smoke() else 64
    new_tokens = 4 if smoke() else 16
    loop_tokens = 2 if smoke() else 4

    clf = LlamaZeroShotClassifier(
        config=LlamaConfig.tiny(), max_prompt_len=64, seed=0
    )
    prompts = [f"song lyric number {i} about love and rain" for i in
               range(n_prompts)]

    clf.generate_batch(prompts, max_new_tokens=new_tokens)  # compile
    scan_s, _ = timed(
        lambda: clf.generate_batch(prompts, max_new_tokens=new_tokens) or 0,
        repeats=2,
    )
    scan_tokens_per_s = n_prompts * new_tokens / scan_s

    clf.generate(prompts[0], max_new_tokens=loop_tokens)  # compile
    start = time.perf_counter()
    clf.generate(prompts[0], max_new_tokens=loop_tokens)
    loop_s = time.perf_counter() - start
    loop_tokens_per_s = loop_tokens / loop_s

    return {
        "suite": "generation",
        **device_info(),
        "smoke": smoke(),
        "config": "LlamaConfig.tiny (topology-complete smoke model)",
        "scan_decode": {
            "prompts": n_prompts,
            "new_tokens": new_tokens,
            "seconds": round(scan_s, 3),
            "tokens_per_s": round(scan_tokens_per_s, 1),
        },
        "per_token_loop": {
            "prompts": 1,
            "new_tokens": loop_tokens,
            "seconds": round(loop_s, 3),
            "tokens_per_s": round(loop_tokens_per_s, 1),
        },
        "scan_advantage": round(scan_tokens_per_s / loop_tokens_per_s, 1),
    }
