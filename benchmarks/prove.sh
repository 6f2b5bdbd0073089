#!/bin/sh
# Prove one cell on the chip in ONE call: new processes one after the other,
# sharing the compile cache. No process here touches jax but the runs.
#
#   chiprun --timeout 3000 -- sh benchmarks/prove.sh <cell> <seconds> <runs-a-set> [first-seed]
#
# Run 0 is cold (it compiles; its set-up is recorded apart), then two sets of
# <runs-a-set> runs with the same seeds in both sets, then one traced run.
# Each run's whole output goes to $BENCH_OUT/prove/<cell>/<run>.log (BENCH_OUT
# is chiprun_out unless set: set it when running from an unpacked archive in a
# subdirectory); the last line of each is echoed, and benchmarks/spread.py
# sums them up.
cell=$1; seconds=$2; runs=$3; seed0=${4:-2147483700}
out=${BENCH_OUT:-chiprun_out}/prove/$cell
mkdir -p "$out"
one() {  # one <label> <seed> <trace>
    python3 benchmarks/run.py --workload "$cell" --seed "$2" \
        --seconds "$seconds" --trace "$3" > "$out/$1.log" 2> "$out/$1.err"
    echo "$1 seed=$2 rc=$? $(tail -n 1 "$out/$1.log")"
}
one cold "$seed0" 0
for set in a b; do
    i=1
    while [ "$i" -le "$runs" ]; do
        one "$set$i" $((seed0 + i)) 0
        i=$((i + 1))
    done
done
one traced $((seed0 + 1)) 1
python3 benchmarks/spread.py "$out"
