#!/bin/bash
# two sets of six runs of one cell on the same six seeds, then one traced run:
#   bash benchmark/tools/full_sets.sh <cell> <seconds> ["six seeds"] [traced seed]
cell=$1; seconds=$2
seeds=${3:-"9001 2147489002 9003 2147583004 9005 2147683006"}; traced=${4:-9107}
mkdir -p chiprun_out/sets_$cell
for set in 1 2; do
  for seed in $seeds; do
    python3 benchmark/run.py --workload $cell --seed $seed --seconds $seconds --trace 0 > chiprun_out/sets_$cell/set${set}_$seed.out 2> chiprun_out/sets_$cell/set${set}_$seed.err
    echo "set $set seed $seed rc=$? $(tail -n 1 chiprun_out/sets_$cell/set${set}_$seed.out | cut -c1-420)"
  done
done
python3 benchmark/run.py --workload $cell --seed $traced --seconds $seconds --trace 1 > chiprun_out/sets_$cell/traced.out 2> chiprun_out/sets_$cell/traced.err
echo "traced rc=$? $(tail -n 1 chiprun_out/sets_$cell/traced.out | cut -c1-2500)"
python3 benchmark/tools/spread.py chiprun_out/sets_$cell/set1_*.out -- chiprun_out/sets_$cell/set2_*.out | cut -c1-900
grep -h "compared" chiprun_out/sets_$cell/set*.out | python3 -c "
import sys, json, collections
seen = collections.defaultdict(list)
for line in sys.stdin:
    row = json.loads(line[11:]); seen[row['name']].append(row['value'])
for name, values in seen.items(): print('[readings]', name, min(values), max(values), len(values))"
