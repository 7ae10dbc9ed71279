#!/bin/bash
# Parent against change in one cell of BENCHMARK.json, in ONE chip call, so
# both sides are measured on the same chip (PERF.md section 6 quotes its
# pairs).  Run from the repo root after
#
#   mkdir -p .checkout .scratch/final
#   git archive HEAD | tar -x -C .checkout                    # the parent
#   git add -A && git archive $(git write-tree) | tar -x -C .scratch/final
#   chiprun --timeout 3000 -- bash benchmarks/cell_ab.sh \
#       llm_sentiment_releases "P0 C0 C0 P0" 2463534242
#
# usage: cell_ab.sh <workload> <legs> <seed base> [pair|solo]
#   legs  P = parent (.checkout), C = change (.scratch/final); the digit is
#         --trace.  "pair": consecutive legs share a seed (the two sides of
#         one comparison); "solo": every leg has its own.
# Each leg's result line is printed and kept, with its warm-up job's manifest
# (`profiling.compiles`: the `hlo_fingerprint` of every compiled program) and
# a traced leg's reduced trace (by XLA's names, and by the program's scopes:
# `python3 perfbench/tools/scope_table.py <leg>.scope_reduced.json` prints
# the second), and the count of steps a compact rung (`<leg>.rungs.txt`),
# under chiprun_out/ab/.  A traced leg keeps a compile cache of its side's
# own (ROADMAP I10: the machine's cache hands a kernel-free program back
# with the scopes of whichever tree wrote the entry).
wl=$1; order=$2; seed=$3; mode=${4:-pair}
root=$PWD
mkdir -p chiprun_out/ab
i=0
for leg in $order; do
  side=${leg:0:1}; tr=${leg:1:1}
  if [ "$mode" = "pair" ]; then k=$((i/2)); else k=$i; fi
  s=$((seed + 7919 * k + 1000003 * tr))
  if [ "$side" = "P" ]; then dir=.checkout; else dir=.scratch/final; fi
  out=$root/chiprun_out/ab/${wl}_${leg}_seed${s}
  ( if [ "$tr" = "1" ]; then
      export JAX_COMPILATION_CACHE_DIR=$root/.scratch/jax_cache_$side
    fi
    cd $dir && python3 perfbench/run.py --workload $wl --seed $s \
        --seconds 50 --trace $tr > $out.out 2> $out.err
    echo "rc=$?" >> $out.out
    cp perfbench/out/$wl/run/warmup/sentiment/run_manifest.json \
       $out.manifest.json
    # the compact rungs the leg's steps met (`moe_capacity` on `compute`)
    cat perfbench/out/$wl/run/job*/*/telemetry.jsonl 2>/dev/null \
      | grep -o '"moe_capacity": [0-9]*' | sort | uniq -c | tr '\n' ';' \
      > $out.rungs.txt
    if [ "$tr" = "1" ]; then
      cp perfbench/out/$wl/trace_reduced.json $out.trace_reduced.json
      cp perfbench/out/$wl/scope_reduced.json $out.scope_reduced.json \
        2>/dev/null  # a parent older than PR 35 writes none
    fi )
  echo "== $leg seed $s rungs: $(cat $out.rungs.txt)"
  tail -n 2 $out.out | cut -c1-2500
  i=$((i+1))
done
