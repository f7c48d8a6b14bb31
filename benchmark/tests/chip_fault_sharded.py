"""python benchmark/tests/chip_fault_sharded.py --workload <cell> --fault <name> --seeds 1,2

`chip_fault.py` with the faults of a row-sharded booster added
(faults_sharded.py: `shard_left_out`, `no_exchange`): one warm period and
one timed period at the cell's own size on its chips, the program broken
underneath or, for `--fault control`, sound and the float8 control judged
in its place.  A control run is a sound timed run of one period: the
driver prints what it timed on standard error.  Readings are in PERF.md."""

import sys

import chip_fault
import faults
import faults_sharded

faults.FAULTS.update(faults_sharded.FAULTS)

if __name__ == "__main__":
    sys.exit(chip_fault.main())
