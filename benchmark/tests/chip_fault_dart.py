"""python benchmark/tests/chip_fault_dart.py --workload <cell> --fault <name> --seeds 1,2

`chip_fault.py` with the faults of a booster that boosts with dropouts added
(faults_dart.py: `undropped_gradients`, `normalize_left_out`,
`learning_rate_for_shrinkage`, `bank_not_carried`, `other_drop_seed`,
`replay_reads_row0`, and the sound `small_bank`): one warm period and the
cell's window at its own size on the chip, the program broken underneath or,
for `--fault control`, sound and the float8 control judged in its place.  One
fault a process: a step traced with a fault stays in the program's step
cache.  Readings are in PERF.md."""

import sys

import chip_fault
import faults
import faults_dart

faults.FAULTS.update(faults_dart.FAULTS)
faults.FAULTS.update(faults_dart.SOUND)

if __name__ == "__main__":
    sys.exit(chip_fault.main())
