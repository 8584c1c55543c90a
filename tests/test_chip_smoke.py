"""chip_smoke.py's CPU rehearsal, tiny, so the script cannot rot between
chip runs: the whole served path (daemon child → scribe → WAL → device
ring → HTTP reads == in-memory oracle) at a 2^12 ring and a few hundred
spans. The chip run itself is made through the chip tool; a rehearsal
must never claim the chip."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_rehearsal_passes_and_names_the_cpu():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"),
         "--rehearse", "--capacity", str(1 << 12), "--spans", "300"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    lines = proc.stdout.strip().splitlines()
    assert "native_codec: built" in lines
    result = json.loads(lines[-1])
    assert result["ok"] is True
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == 1
    assert "tpu" not in lines[-1].lower()
