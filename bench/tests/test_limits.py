"""Each configuration's limit against the chip readings it was set from,
through the harness's own ``correct`` expression: the widest gap that sound
runs of the program gave passes, and the smallest that the control (the
reference in fp8, its first choices in the served tokens' place) gave does
not."""
import glob
import json
import os

import pytest

from bench.lib import harness

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = sorted(glob.glob(os.path.join(ROOT, "configs", "*.json")))


def _checked():
    out = []
    for path in CONFIGS:
        with open(path) as f:
            check = json.load(f)["check"]
        if check.get("max_gap") is not None:
            out.append(pytest.param(check, id=os.path.basename(path)))
    return out


@pytest.mark.parametrize("check", _checked())
def test_limit_separates_its_readings(check):
    r = check["readings"]
    need = int(check["min_tokens"])
    assert harness.judge(check, r["sound"], 0, need)[1]
    assert not harness.judge(check, r["control"], 0, need)[1]
    # room on both sides, the more of it above the sound runs
    assert r["sound"] < check["max_gap"] < r["control"]
    assert check["max_gap"] - r["sound"] > r["control"] - check["max_gap"]
    # too few tokens compared, or a request that failed, is never correct
    assert not harness.judge(check, r["sound"], 0, need - 1)[1]
    assert not harness.judge(check, r["sound"], 1, need)[1]
