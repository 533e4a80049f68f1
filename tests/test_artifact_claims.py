"""Claims hygiene in the tier-1 suite: every numeric claim README.md makes
must match the committed artifact it is anchored to
(tools/check_artifact_claims.py)."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import check_artifact_claims  # noqa: E402


def test_readme_claims_match_artifacts():
    failures = check_artifact_claims.check()
    assert not failures, "\n".join(failures)


def test_every_claim_is_anchored():
    # each claim pattern names both a value and a round anchor, so a claim
    # can never silently drift to a different round's artifact
    import re

    for c in check_artifact_claims.CLAIMS:
        groups = re.compile(c.pattern, re.DOTALL).groupindex
        assert "val" in groups and "round" in groups, c.label


def test_mismatch_is_detected(tmp_path):
    # a README claiming a wrong A/B speedup must fail the checker
    with open(os.path.join(REPO, "README.md")) as f:
        text = f.read()
    import re

    bad = re.sub(
        r"beating measured DP by [\d.]+x",
        "beating measured DP by 9.99x",
        text,
        count=1,
    )
    assert bad != text
    p = tmp_path / "README.md"
    p.write_text(bad)
    failures = check_artifact_claims.check(str(p))
    assert any("A/B transformer searched win" in f for f in failures)


def test_serving_family_mismatch_is_detected(tmp_path):
    # the SERVE_r* family (ISSUE 12): a wrong continuous-over-static
    # ratio must fail against the committed serving artifact
    with open(os.path.join(REPO, "README.md")) as f:
        text = f.read()
    import re

    bad = re.sub(
        r"continuous\s+sustains \*\*[\d.]+x\*\* static",
        "continuous sustains **9.99x** static",
        text,
        count=1,
    )
    assert bad != text
    p = tmp_path / "README.md"
    p.write_text(bad)
    failures = check_artifact_claims.check(str(p))
    assert any("continuous-over-static" in f for f in failures)


def test_drift_family_mismatch_is_detected(tmp_path):
    # the DRIFT_r* family (ISSUE 18): a wrong advisory trigger step must
    # fail against the committed drift artifact
    with open(os.path.join(REPO, "README.md")) as f:
        text = f.read()
    import re

    bad = re.sub(
        r"ReplanAdvisory\s+at\s+step\s+\*\*\d+\*\*",
        "ReplanAdvisory at step **9999**",
        text,
        count=1,
    )
    assert bad != text
    p = tmp_path / "README.md"
    p.write_text(bad)
    failures = check_artifact_claims.check(str(p))
    assert any("advisory trigger step" in f for f in failures)


def test_transition_family_mismatch_is_detected(tmp_path):
    # the TRN_r* family (ISSUE 19): a wrong degraded-grid pair count
    # must fail against the committed transition-audit artifact
    with open(os.path.join(REPO, "README.md")) as f:
        text = f.read()
    import re

    bad = re.sub(
        r"all\s+\*\*\d+\*\*\s+seed-template\s+pairs\s+verify",
        "all **47** seed-template pairs verify",
        text,
        count=1,
    )
    assert bad != text
    p = tmp_path / "README.md"
    p.write_text(bad)
    failures = check_artifact_claims.check(str(p))
    assert any("degraded-grid swappable" in f for f in failures)


def test_dropped_claim_text_fails(tmp_path):
    # deleting an anchored claim from the README is itself a failure —
    # silently dropping a checked claim is how stale numbers sneak back in
    with open(os.path.join(REPO, "README.md")) as f:
        text = f.read()
    bad = text.replace("decisive rank-inversion", "rank-inversion")
    assert bad != text
    p = tmp_path / "README.md"
    p.write_text(bad)
    failures = check_artifact_claims.check(str(p))
    assert any("not found" in f for f in failures)


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))
