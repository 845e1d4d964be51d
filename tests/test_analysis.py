"""repro.analysis: rule framework, fixtures per rule, HLO auditors, the
compiled-artifact trace audit, and the lint CLI.

The two fixture trees under ``tests/fixtures/analysis/`` mirror the
src/repro layout so the rules' structural ``only``/``exclude`` scoping
applies to them exactly as it does to the real tree:

* ``bad_tree`` seeds one violation per rule (plus a reason-less allow
  marker) — every rule must fire, at the right file and line;
* ``clean_tree`` holds the clean twin of each pattern, every structural
  exemption (compat.py, serving/cache_backend.py, kernels/ops.py) and
  both allowlist escape-hatch forms — nothing may fire.
"""
import json
import os
import pathlib
import re
import subprocess
import sys

import pytest

from repro.analysis import REGISTRY, Finding, SRC_ROOT, run_rules
from repro.analysis import hlo

REPO = pathlib.Path(__file__).resolve().parents[1]
FIX = pathlib.Path(__file__).resolve().parent / "fixtures" / "analysis"
BAD = FIX / "bad_tree"
CLEAN = FIX / "clean_tree"

EXPECTED_RULES = {"compat-api", "cache-mode-dispatch", "interpret-literal",
                  "pallas-call", "host-sync", "bare-jit",
                  "allocator-internals", "cache-length-mutation",
                  "swap-arena-internals"}


# ---------------------------------------------------------------------------
# Registry + the real tree
# ---------------------------------------------------------------------------


def test_registry_exposes_the_invariants():
    assert EXPECTED_RULES <= set(REGISTRY)
    for rule in REGISTRY.values():
        assert rule.description


def test_real_tree_is_clean():
    # the CI lint lane runs the same thing as `lint --strict`
    findings = run_rules()
    assert findings == [], "\n".join(str(f) for f in findings)


# ---------------------------------------------------------------------------
# Fixture trees: every rule fires on its seeded violation, stays quiet on
# the clean twin (including the allowlist escape hatch)
# ---------------------------------------------------------------------------

BAD_EXPECT = {
    "core/sp.py": {"compat-api"},
    "models/attention.py": {"cache-mode-dispatch"},
    "kernels/flash.py": {"interpret-literal"},
    "serving/fastpath.py": {"pallas-call"},
    "serving/steps.py": {"host-sync"},
    "serving/engine.py": {"bare-jit"},
    "serving/sched.py": {"allocator-internals"},
    "serving/spec.py": {"cache-length-mutation"},
    "serving/preempt.py": {"swap-arena-internals"},
    # reason-less marker: reported AND the suppression does not apply
    "serving/cache_backend.py": {"host-sync", "lint-allow"},
}


def test_bad_tree_every_rule_fires_where_seeded():
    by_path = {}
    for f in run_rules(BAD):
        by_path.setdefault(f.path, set()).add(f.rule)
    assert by_path == BAD_EXPECT


def test_bad_tree_findings_carry_real_lines_and_messages():
    findings = run_rules(BAD, rules=["host-sync"])
    steps = [f for f in findings if f.path == "serving/steps.py"]
    # .item / np.asarray / float(traced) / jax.device_get, one per line
    assert [f.line for f in steps] == [7, 8, 9, 10]
    assert str(steps[0]).startswith("serving/steps.py:7: [host-sync]")
    assert steps[0].to_dict()["rule"] == "host-sync"


def test_interpret_literal_catches_annotated_default_and_call_site():
    findings = run_rules(BAD, rules=["interpret-literal"],
                         files=[BAD / "kernels" / "flash.py"])
    assert len(findings) == 2  # `interpret: bool = True` + `interpret=True`


def test_bare_jit_catches_decorator_call_and_partial_forms():
    findings = run_rules(BAD, rules=["bare-jit"],
                         files=[BAD / "serving" / "engine.py"])
    assert len(findings) == 3


def test_clean_tree_is_quiet():
    findings = run_rules(CLEAN)
    assert findings == [], "\n".join(str(f) for f in findings)


def test_allowlist_escape_hatch_both_forms():
    # the clean steps.py contains two real hazards, both allowlisted
    # (inline marker and comment-line-above marker) with reasons
    text = (CLEAN / "serving" / "steps.py").read_text()
    assert "device_get" in text and "float(" in text
    assert run_rules(CLEAN, files=[CLEAN / "serving" / "steps.py"]) == []


def test_allow_marker_without_reason_is_reported_not_honored():
    findings = run_rules(BAD, files=[BAD / "serving" / "cache_backend.py"])
    assert {f.rule for f in findings} == {"host-sync", "lint-allow"}


def test_rule_selection_and_unknown_rule():
    only = run_rules(BAD, rules=["pallas-call"])
    # meta findings (marker hygiene) always ride along
    assert {f.rule for f in only} == {"pallas-call", "lint-allow"}
    with pytest.raises(KeyError, match="unknown rule"):
        run_rules(BAD, rules=["not-a-rule"])


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def test_cli_strict_clean_on_real_tree_nonzero_on_bad_tree(tmp_path, capsys):
    from repro.analysis import lint as lint_cli

    assert lint_cli.main(["--strict"]) == 0
    assert "clean" in capsys.readouterr().out

    report = tmp_path / "lint.json"
    rc = lint_cli.main(["--strict", "--root", str(BAD),
                        "--json", str(report)])
    assert rc == 1
    payload = json.loads(report.read_text())
    assert payload["strict"] and payload["root"] == str(BAD)
    assert set(payload["rules"]) == set(REGISTRY)
    fired = {f["rule"] for f in payload["findings"]}
    assert EXPECTED_RULES | {"lint-allow"} == fired
    for f in payload["findings"]:
        assert set(f) == {"path", "line", "rule", "message"}
    # without --strict findings are reported but don't fail the run
    assert lint_cli.main(["--root", str(BAD), "--json", "-"]) == 0
    out = capsys.readouterr().out
    assert "finding(s)" in out


def test_cli_rule_filter_and_list_rules(capsys):
    from repro.analysis import lint as lint_cli

    assert lint_cli.main(["--list-rules"]) == 0
    listed = capsys.readouterr().out
    for rid in EXPECTED_RULES:
        assert rid in listed
    rc = lint_cli.main(["--strict", "--root", str(BAD), "--rule", "bare-jit"])
    assert rc == 1
    out = capsys.readouterr().out
    assert "bare-jit" in out and "pallas-call" not in out


def test_cli_module_entrypoint():
    # the CI lint lane runs exactly this invocation
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint", "--strict"],
        capture_output=True, text=True, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "clean" in proc.stdout


# ---------------------------------------------------------------------------
# HLO auditors
# ---------------------------------------------------------------------------

SAMPLE_HLO = """\
HloModule jit_decode, is_scheduled=true, input_output_alias={ {0}: (2, {}, \
may-alias), {1}: (4, {}, may-alias) }, entry_computation_layout=...

ENTRY %main (p0: f32[8,128]) -> f32[16,128] {
  %p0 = f32[8,128]{1,0} parameter(0)
  %all-gather.5 = f32[16,128]{1,0} all-gather(f32[8,128]{1,0} %p0), \
replica_groups={{0,1}}, dimensions={0}
  %all-reduce.1 = f32[8,128]{1,0} all-reduce(f32[8,128]{1,0} %p0), \
to_apply=%add
  ROOT %copy.9 = f32[16,128]{1,0} copy(f32[16,128]{1,0} %all-gather.5)
}
"""

START_HLO = """\
HloModule jit_step
ENTRY %e (p0: bf16[4,8]) -> bf16[8,8] {
  %ag = (bf16[4,8]{1,0}, bf16[8,8]{1,0}) all-gather-start(bf16[4,8]{1,0} \
%p0), replica_groups={{0,1}}, dimensions={0}
  ROOT %d = bf16[8,8]{1,0} all-gather-done((bf16[4,8]{1,0}, bf16[8,8]{1,0}) \
%ag)
}
"""


def test_find_collectives_and_largest_allgather():
    cs = hlo.find_collectives(SAMPLE_HLO)
    assert [(c.op, c.bytes) for c in cs] == [
        ("all-gather", 16 * 128 * 4), ("all-reduce", 8 * 128 * 4)]
    assert cs[0].line == 5  # real HLO text line
    assert hlo.largest_allgather_bytes(SAMPLE_HLO) == 16 * 128 * 4
    # tuple results of -start ops take the largest element, not the sum
    assert hlo.largest_allgather_bytes(START_HLO) == 8 * 8 * 2


def _legacy_largest_allgather_bytes(hlo_text):
    """The exact regex scan launch/dryrun.py shipped before the refactor —
    the shared auditor must stay byte-compatible with it."""
    dtb = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
           "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
           "f64": 8}
    biggest = 0
    call = re.compile(r"=\s*(.*?)\s*all-gather(?:-start|-done)?\(", re.S)
    for line in hlo_text.splitlines():
        m = call.search(line)
        if not m:
            continue
        for dt, dims in re.findall(r"(\w+)\[([0-9,]*)\]", m.group(1)):
            if dt not in dtb:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            biggest = max(biggest, n * dtb[dt])
    return biggest


@pytest.mark.parametrize("sample", [SAMPLE_HLO, START_HLO, "no collectives"])
def test_dryrun_byte_compat(sample):
    assert hlo.largest_allgather_bytes(sample) == \
        _legacy_largest_allgather_bytes(sample)


def test_dryrun_consumes_the_shared_auditor():
    src = (REPO / "src/repro/launch/dryrun.py").read_text()
    assert "from repro.analysis.hlo import largest_allgather_bytes" in src
    assert "def _largest_allgather_bytes" not in src


def test_input_output_alias_parsing():
    assert hlo.input_output_aliases(SAMPLE_HLO) == [((0,), 2), ((1,), 4)]
    assert hlo.aliased_parameter_numbers(SAMPLE_HLO) == [2, 4]
    assert hlo.input_output_aliases(START_HLO) == []


def test_audit_hlo_big_allgather_and_missing_alias():
    cap = 16 * 128 * 4
    hot = hlo.audit_hlo(SAMPLE_HLO, label="decode", max_allgather_bytes=cap)
    assert [f.rule for f in hot] == ["hlo-big-allgather"]
    assert hot[0].path == "decode" and hot[0].line == 5
    assert hlo.audit_hlo(SAMPLE_HLO, label="decode",
                         max_allgather_bytes=cap + 1) == []
    assert hlo.audit_hlo(SAMPLE_HLO, label="decode",
                         expect_alias_params=(2, 4)) == []
    missing = hlo.audit_hlo(SAMPLE_HLO, label="decode",
                            expect_alias_params=(3,))
    assert [f.rule for f in missing] == ["hlo-missing-alias"]
    big_ar = hlo.audit_hlo(SAMPLE_HLO, label="decode",
                           max_collective_bytes={"all-reduce": 1})
    assert [f.rule for f in big_ar] == ["hlo-big-collective"]


POOL_HLO = """\
HloModule jit_decode
%fused (param_0.1: f32[2,17,8,4,32]) -> f32[17,8,4,32] {
  %param_0.1 = f32[2,17,8,4,32]{4,3,2,1,0} parameter(0)
  ROOT %ds = f32[17,8,4,32]{3,2,1,0} dynamic-slice(f32[2,17,8,4,32]{4,3,2,1,0} \
%param_0.1, s32[] %i), dynamic_slice_sizes={1,17,8,4,32}
}
ENTRY %e (p0: f32[2,17,8,4,32]) -> f32[2,17,8,4,32] {
  %p0 = f32[2,17,8,4,32]{4,3,2,1,0} parameter(0)
  %sc = f32[2,17,8,4,32]{4,3,2,1,0} scatter(%p0, %idx, %upd), to_apply=%set
  %g = f32[16,1,1,8,4,32]{5,4,3,2,1,0} gather(%sc, %tab), slice_sizes={1,1,8,4,32}
  %b = f32[2,17,8,4,32]{4,3,2,1,0} broadcast(f32[] %z), dimensions={}
  %cs = (f32[2,17,8,4,32]{4,3,2,1,0}, f32[2,17,8,4,32]{4,3,2,1,0}, u32[]) \
copy-start(%sc)
  %w = (s32[], f32[2,17,8,4,32]{4,3,2,1,0}) while(%t), condition=%c, body=%bd
  %u = f32[2,17,8,4,32]{4,3,2,1,0} dynamic-update-slice(%b, %x, %i, %i)
  %m = f32[34,8,4,32]{3,2,1,0} copy(%u)
  ROOT %cp = f32[2,17,8,4,32]{4,3,2,1,0} copy(%u)
}
"""


def test_pool_copy_findings_flag_copies_and_layer_slices():
    found = hlo.pool_copy_findings(POOL_HLO, label="decode",
                                   pools=["f32[2,17,8,4,32]"])
    assert {f.rule for f in found} == {"hlo-pool-copy"}
    # the layer-shaped slice, then broadcast / copy-start / DUS / copy on
    # the stacked pool, and a copy of it with all layers' pages merged;
    # the parameter, scatter, gather and while are quiet
    assert [f.line for f in found] == [4, 10, 11, 13, 14, 15]
    assert "one layer's slice" in found[0].message
    assert "whole stacked" in found[1].message
    assert hlo.pool_copy_findings(POOL_HLO, label="decode",
                                  pools=["f32[2,9,8,4,32]"]) == []


# ---------------------------------------------------------------------------
# Compiled-artifact trace audit (lowers the real jitted serving steps)
# ---------------------------------------------------------------------------


def test_trace_audit_decode_and_prefill_clean_with_donation():
    from repro.analysis.trace_audit import audit_serving_step

    findings, report = audit_serving_step("fp", False, donate=True)
    assert findings == [], "\n".join(str(f) for f in findings)
    labels = [s["label"] for s in report["steps"]]
    assert labels == ["decode_chunk[fp]", "prefill_chunk[fp]"]
    for step in report["steps"]:
        assert step["donated"] and step["alias_entries"] > 0
    # jnp route: the Pallas wrappers must not have traced
    assert report["kernel_invocations"] == {}


@pytest.mark.parametrize("mode", ["paged", "paged_vq"])
def test_trace_audit_paged_pools_stay_in_place(mode):
    """Donated decode and prefill chunks reach the stacked page pools only
    through the token scatter and the page gather: no copy, broadcast or
    dynamic-update-slice of a stacked pool, and no op on one layer's pool
    (the per-layer xs/ys path compiles to all of these)."""
    from repro.analysis.trace_audit import DEFAULT_MATRIX, audit_serving_step

    assert (mode, False, False, True) in DEFAULT_MATRIX  # lint --trace
    findings, report = audit_serving_step(mode, False, donate=True)
    assert findings == [], "\n".join(str(f) for f in findings)
    assert [s["label"] for s in report["steps"]] == [
        f"decode_chunk[{mode}]", f"prefill_chunk[{mode}]"]
    for step in report["steps"]:
        assert step["donated"] and step["alias_entries"] > 0


def test_trace_audit_pallas_engagement_and_big_allgather_guard():
    from repro.analysis.trace_audit import audit_serving_step

    findings, report = audit_serving_step("fp", True)
    assert findings == [], "\n".join(str(f) for f in findings)
    assert report["kernel_invocations"].get("decode_attention", 0) >= 1
    assert report["kernel_invocations"].get("chunk_attention", 0) >= 1
    # the dryrun invariant rides the same auditor: an embed-sized
    # all-gather in the decode step would have been a finding above
    for step in report["steps"]:
        assert step["largest_allgather_bytes"] == 0


def test_trace_audit_flags_silent_fallback_and_bypass():
    from repro.analysis.trace_audit import engagement_findings

    silent = engagement_findings({}, use_pallas=True, label="t")
    assert [f.rule for f in silent] == ["kernel-engagement"]
    bypass = engagement_findings({"decode_attention": 1}, use_pallas=False,
                                 label="t")
    assert [f.rule for f in bypass] == ["kernel-engagement"]
    assert engagement_findings({"decode_attention": 1}, use_pallas=True,
                               label="t") == []
    assert engagement_findings({}, use_pallas=False, label="t") == []
