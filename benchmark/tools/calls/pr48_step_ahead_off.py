"""PR 48: one run of a cell with PR 47's step ahead taken off again, so the
request phases this PR records can be read under the scheduling of PR 47's
parent too: the decode tick in which a row ends by length sends no step
ahead (``_next_decode_rows`` answers only when every row of the step goes
on, the parent's ``_same_rows_next_tick``).  Everything else is this tree's.

    python3 benchmark/tools/calls/pr48_step_ahead_off.py --workload <cell> --seed <n> --seconds 51 --trace 1
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))))

from deepspeed_tpu.serving.scheduler import ContinuousBatchScheduler  # noqa: E402

_rows = ContinuousBatchScheduler._next_decode_rows


def _only_when_every_row_goes_on(self, step, packed):
    on = _rows(self, step, packed)
    return on if len(on) == len(step.rows) else []


ContinuousBatchScheduler._next_decode_rows = _only_when_every_row_goes_on

from benchmark import run  # noqa: E402

if __name__ == "__main__":
    sys.exit(run.main())
