"""Round-trip test for the golden-refresh script itself.

scripts/refresh_goldens.py is the glue the CI golden-drift job depends
on: it must emit snapshots in exactly the schema golden.py/
test_goldens.py consume, or the drift check degenerates into a
confusing golden-assert failure. Run one scenario through the script
into a tmpdir and pin the emitted JSON against the committed snapshot
(same schema, same metrics within golden tolerance).
"""

import dataclasses
import json
import math
import os
import subprocess
import sys

from repro.serving.golden import ATOL, GOLDEN_POLICY, RTOL, golden_specs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "scripts", "refresh_goldens.py")
GOLDEN_DIR = os.path.join(REPO, "tests", "goldens")
SCENARIO = "multi-cluster"  # also snapshotted under estimate-routing/


def _assert_matches_committed(emitted_path: str, committed_path: str) -> dict:
    with open(emitted_path) as f:
        emitted = json.load(f)
    with open(committed_path) as f:
        committed = json.load(f)
    # exact snapshot schema golden.py / test_goldens.py consume
    assert set(emitted) == {"policy", "spec", "summary"}
    assert emitted["policy"] == GOLDEN_POLICY
    assert emitted["spec"] == dataclasses.asdict(golden_specs()[SCENARIO])
    assert set(emitted["summary"]) == set(committed["summary"])
    for key, want in committed["summary"].items():
        got = emitted["summary"][key]
        assert math.isclose(got, want, rel_tol=RTOL, abs_tol=ATOL), (
            f"{os.path.basename(emitted_path)}: {key} got {got!r}, "
            f"committed {want!r}"
        )
    return emitted


def test_refresh_goldens_round_trip(tmp_path):
    proc = subprocess.run(
        [sys.executable, SCRIPT, "--only", SCENARIO,
         "--out-dir", str(tmp_path)],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr
    assert SCENARIO in proc.stdout

    _assert_matches_committed(
        str(tmp_path / f"{SCENARIO}.json"),
        os.path.join(GOLDEN_DIR, f"{SCENARIO}.json"),
    )
    # the estimate-routing A/B snapshot rides along for this scenario
    _assert_matches_committed(
        str(tmp_path / "estimate-routing" / f"{SCENARIO}.json"),
        os.path.join(GOLDEN_DIR, "estimate-routing", f"{SCENARIO}.json"),
    )
    # and nothing else is written
    written = sorted(str(p.relative_to(tmp_path))
                     for p in tmp_path.rglob("*.json"))
    assert written == [f"estimate-routing/{SCENARIO}.json",
                       f"{SCENARIO}.json"]


def test_refresh_goldens_rejects_unknown_scenario(tmp_path):
    proc = subprocess.run(
        [sys.executable, SCRIPT, "--only", "no-such-scenario",
         "--out-dir", str(tmp_path)],
        capture_output=True, text=True, cwd=REPO,
    )
    assert proc.returncode != 0
    assert "no-such-scenario" in proc.stderr
    assert not list(tmp_path.iterdir())  # nothing written on bad input
