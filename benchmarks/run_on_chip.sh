#!/bin/sh
# One command for a healthy-chip measurement session: the headline bench
# (writes the driver-format JSON line last), then the full scale ladder
# including the 10M row. Logs land next to this script with timestamps so
# BENCH.md can be refreshed from them afterwards.
#
#   sh benchmarks/run_on_chip.sh
#
# The steps run one after another, so one process at a time holds the
# chip. bench.py fails without a TPU; its exit code gates the ladder
# (POSIX sh has no pipefail, so capture the status before tee-ing the
# output).
set -u
cd "$(dirname "$0")/.."
stamp=$(date +%Y%m%d-%H%M%S)
log="benchmarks/chip-$stamp.log"
tmp="benchmarks/.chip-$stamp.tmp"
echo "# chip session $stamp" | tee "$log"
python bench.py > "$tmp" 2>&1
bench_rc=$?
tee -a "$log" < "$tmp"
rm -f "$tmp"
if [ $bench_rc -ne 0 ]; then
    echo "# bench.py failed (rc=$bench_rc) — skipping the ladder" | tee -a "$log"
    exit $bench_rc
fi
python benchmarks/ladder.py --full 2>&1 | tee -a "$log"
echo "# session log: $log"
