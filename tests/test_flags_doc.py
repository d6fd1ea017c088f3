"""README/flags drift lint: the flags table grew ~40 rows across 16 PRs
with no guard — flags.flags_doc_issues() cross-references it against
the DEFS registry; a missing, stale, or duplicated row fails here AND
in ``tools/lint_program.py --flags`` (same helper)."""

import os

import pytest

from paddle_tpu import flags

README = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "README.md")


def test_readme_flags_table_in_sync():
    issues = flags.flags_doc_issues(README)
    assert not issues, "\n".join(issues)


def test_drift_is_detected(tmp_path):
    # a table missing a real flag AND carrying a stale row: both caught
    fake = tmp_path / "README.md"
    fake.write_text(
        "| flag | default | effect |\n|---|---|---|\n"
        "| `verify` | off | static verifier |\n"
        "| `no_such_flag_ever` | off | stale |\n"
        "| `verify` | off | documented twice |\n")
    issues = flags.flags_doc_issues(str(fake))
    text = "\n".join(issues)
    assert "opt_level" in text            # missing row
    assert "no_such_flag_ever" in text    # stale row
    assert "2 times" in text              # duplicate row
    assert flags.flags_doc_issues(str(tmp_path / "absent.md"))


def test_layout_flags_are_gone():
    # the registry is what the README table and --flags are checked
    # against: a removed flag is unknown to it, not silently ignored
    assert len(flags.DEFS) == 57
    assert not [name for name in flags.DEFS if "layout" in name]
    with pytest.raises(KeyError):
        flags.set_flags({"layout": "nhwc"})


def test_memory_planning_flags_are_gone():
    # opt level 3's budget and re-plan tolerance went with the planner
    assert len(flags.DEFS) == 57
    assert "hbm_budget_frac" not in flags.DEFS
    assert "replan_tolerance" not in flags.DEFS
    with pytest.raises(KeyError):
        flags.set_flags({"replan_tolerance": 0.25})
