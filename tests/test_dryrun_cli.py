"""End-to-end dry-run CLI smoke: one real 512-device lowering (the smallest
arch x shape) in a subprocess, validating the full launch path + JSON
contract.  ~60 s; the 80-combo production evidence lives in results/."""
import json

import pytest
from _subproc import run_python


@pytest.mark.slow
def test_dryrun_cli_smallest_combo():
    proc = run_python(["-m", "repro.launch.dryrun", "--arch", "whisper-base",
                       "--shape", "decode_32k"], timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["status"] == "ok"
    assert rec["devices"] == 256
    assert rec["mesh"] == "16x16"
    assert rec["flops_per_device"] > 0
    assert rec["collective_bytes_per_device"]["total"] >= 0
    assert {"in_loop", "outside"} <= set(rec["collective_bytes_per_device"])
    assert rec["memory"]["peak_bytes"] > 0
