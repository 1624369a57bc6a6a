"""The control (the reference with every product's operands rounded to
fp8, the precision below bf16) reads above the limit that sound runs of
the program stay under, at a size a test run can hold."""
import io
import json
from contextlib import redirect_stdout

from tiny_cell import CELL, WORKLOAD, make_bench


def test_control_fails_the_limit_the_program_meets(tmp_path):
    from bench import control

    out = io.StringIO()
    with redirect_stdout(out):
        rc = control.main(["--workload", WORKLOAD, "--seeds", "21,22",
                           "--seconds", "1.5", "--rehearsal", "--benchmark",
                           str(make_bench(tmp_path))])
    assert rc == 0
    limit = CELL["correct"]["widest_logit_gap_limit"]
    for line in out.getvalue().strip().splitlines():
        r = json.loads(line)
        assert r["widest_logit_gap"] <= limit < r["control_widest_logit_gap"]
