#!/bin/bash
# The runs a benchmark PR reports, one cell to a call, a fresh process a run:
#   bash chipbench/sets.sh sets   <tag> <cell> <sets> <seconds> seed...   (--trace 0, every seed once a set)
#   bash chipbench/sets.sh traces <tag> <cell> <seconds> seed...          (--trace 1)
# Each run's output stays under chiprun_out/<tag>/; one summary line a run is printed.
mode=$1; tag=$2; cell=$3; shift 3
if [ "$mode" = sets ]; then sets=$1; secs=$2; shift 2; trace=0; else sets=1; secs=$1; shift 1; trace=1; fi
mkdir -p chiprun_out/$tag
for s in $(seq 1 $sets); do for seed in "$@"; do
  f=chiprun_out/$tag/$cell.$mode$s.$seed
  python3 -m chipbench.run --workload $cell --seed $seed --seconds $secs --trace $trace > $f.out 2> $f.err; rc=$?
  tail -n 1 $f.out | python3 -c "
import json, sys
try:
    d = json.loads(sys.stdin.read()); r = d['run']
    over = {k: v['value'] for k, v in d['compared'].items() if not v['value'] <= v['limit']}
    print('RUN $cell $mode$s $seed rc=$rc correct', d['correct'], {k: round(v['value'], 4) for k, v in d['metrics'].items()},
          'failed', d['failed'], 'mem', d['device']['memory_peak_bytes'],
          {k: round(d['device'][k], 3) for k in ('busy_s', 'window_s') if k in d['device']},
          {k: r[k] for k in ('p99_ms', 'rejected', 'gen_late_max_ms', 'batch_gap_max_ms') if k in r}, 'over', over)
    print('COMPARED', json.dumps({k: v['value'] for k, v in d['compared'].items()}))
    if 'breakdown' in d: print('BREAKDOWN', json.dumps(d['breakdown']))
except Exception as e: print('RUN $cell $mode$s $seed rc=$rc NO RESULT', e)
"
done; done
