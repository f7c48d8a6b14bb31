"""python benchmark/tests/chip_fault_bagged.py --workload <cell> --fault <name> --seeds 1,2

`chip_fault.py` with the faults of a booster that samples added
(faults_bagged.py: `oob_descent_left_out`, `hist_over_all_rows`,
`first_bag_kept`, `feature_mask_ignored`): one warm period and one timed
period at the cell's own size on the chip, the program broken underneath or,
for `--fault control`, sound and the float8 control judged in its place.
One fault a process: a step traced with a fault stays in the program's step
cache.  Readings are in PERF.md."""

import sys

import chip_fault
import faults
import faults_bagged

faults.FAULTS.update(faults_bagged.FAULTS)

if __name__ == "__main__":
    sys.exit(chip_fault.main())
