"""`make bench-zoo`: the scenario sweep, the pid-reuse control arm and
the endurance matrix at full scale, their own results on one JSON line."""

import json
import sys

from parca_agent_tpu.bench_zoo import run_matrix, run_scenario, run_zoo

SEED, SCALE = 1234, 0.5

# A cold process's first window carries the lazy imports (seconds, over
# the rows' 2 s close ceiling): spend it on a row that is not scored.
run_scenario("pid_reuse", SEED, scale=SCALE)
sweep = run_zoo(SEED, scale=SCALE, hardened=True)
control = run_scenario("pid_reuse", SEED, scale=SCALE, hardened=False)
matrix = run_matrix(SEED, scale=SCALE)
print(json.dumps({"sweep": sweep, "control_arm": control, "matrix": matrix},
                 default=repr))
sys.exit(0 if sweep["passed"] and control["passed"] and matrix["passed"]
         else 1)
