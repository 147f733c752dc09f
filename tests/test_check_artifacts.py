"""tools/check_artifacts.py: the one-shot committed-artifact gate.

Two pins: (1) the validator ROSTER covers every tool that carries a
``--check`` mode — a new tool with a forgotten roster entry fails here,
not six PRs later when its artifact silently rots; (2) running the full
roster against the COMMITTED artifacts is green, which is the actual
contract ("every committed artifact's claims are still true against the
current validators") that this tier-1 test makes CI enforce.
"""

import os
import sys

_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_DIR, "tools"))

import check_artifacts  # noqa: E402


def test_roster_covers_every_check_capable_tool():
    tools_dir = os.path.join(_DIR, "tools")
    check_capable = set()
    for name in os.listdir(tools_dir):
        if not name.endswith(".py") or name == "check_artifacts.py":
            continue
        with open(os.path.join(tools_dir, name)) as f:
            if '"--check"' in f.read():
                check_capable.add(f"tools/{name}")
    # a new --check-capable tool must be rostered the PR it lands
    assert check_capable == set(check_artifacts.CHECKS)


def test_all_committed_artifact_validators_green():
    lines = []
    failures = check_artifacts.run_checks(echo=lines.append)
    assert failures == [], "\n".join(lines)
    # one verdict line per roster entry, every one 'ok'
    assert len(lines) == len(check_artifacts.CHECKS)
    assert all(line.endswith("--check: ok") for line in lines)
