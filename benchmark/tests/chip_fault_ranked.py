"""python benchmark/tests/chip_fault_ranked.py --workload <cell> --fault <name> --seeds 1,2

`chip_fault.py` with the faults of a ranking booster added
(faults_ranked.py: `long_queries_left_out`, `stale_positions`): one warm
period and one timed period at the cell's own size on the chip, the program
broken underneath or, for `--fault control`, sound and the float8 control
judged in its place.  One fault a process: a step traced with a fault stays
in the program's step cache.  Readings are in PERF.md."""

import sys

import chip_fault
import faults
import faults_ranked

faults.FAULTS.update(faults_ranked.FAULTS)

if __name__ == "__main__":
    sys.exit(chip_fault.main())
