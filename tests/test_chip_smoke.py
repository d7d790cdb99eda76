"""CPU rehearsal of chip_smoke.py: the same control flow at tiny sizes.

What it proves: every phase passes off-chip, so a failure on the chip
is the chip's; the script still exits non-zero with `"ok": false`,
because the platform check cannot be passed without a TPU; and a phase
whose device path raises takes the whole script down with it.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(*argv, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("XLA_FLAGS", None)  # the smoke sets what its rehearsal needs
    out = subprocess.run([sys.executable, SMOKE, *argv], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=timeout)
    lines = [json.loads(line) for line in out.stdout.splitlines()
             if line.startswith("{")]
    return out, lines


@pytest.fixture(scope="module")
def one_chip():
    return _run("--rehearse", "--seed", "5")


@pytest.fixture(scope="module")
def four_chips():
    return _run("--rehearse", "--chips", "4", "--seed", "5")


def _phase_report(lines, phase):
    reports = [doc for doc in lines
               if doc.get("phase") == phase and "failures" in doc]
    assert len(reports) == 1, reports
    return reports[0]


@pytest.mark.parametrize("phase", ["bulk", "serve", "visibility"])
def test_rehearsal_phase_passes(one_chip, phase):
    out, lines = one_chip
    report = _phase_report(lines, phase)
    assert report["ok"] and report["failures"] == [], \
        (report, out.stderr[-3000:])
    assert report["device"]["platform"] == "cpu"


def test_rehearsal_fails_the_platform_check_and_only_that(one_chip):
    out, lines = one_chip
    assert out.returncode != 0
    last = out.stdout.strip().splitlines()[-1]
    assert json.loads(last) == {
        "ok": False,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    failures = [doc["failure"] for doc in lines if "failure" in doc]
    assert len(failures) == 1 and "not a TPU" in failures[0], failures


def test_rehearsal_used_the_native_encoder_and_the_ladder(one_chip):
    _out, lines = one_chip
    suites = [doc for doc in lines if doc.get("phase") == "bulk"
              and "suite" in doc and "encoder" in doc]
    assert len(suites) == 6 and {d["encoder"] for d in suites} == {"native"}
    ladder = [doc for doc in lines if doc.get("ladder")]
    assert ladder and ladder[0]["residual_oracle_rows"] == 0
    assert ladder[0]["flagged"] > 0 and ladder[0]["oracle_divergent"] == 0


def test_four_chip_rehearsal_runs_the_mesh_phase_alone(four_chips):
    out, lines = four_chips
    assert {doc["phase"] for doc in lines if "phase" in doc} == {"mesh"}
    report = _phase_report(lines, "mesh")
    assert report["ok"], (report, out.stderr[-3000:])
    rows = [doc for doc in lines if "rows_dispatched_per_device" in doc][0]
    assert len(rows["rows_dispatched_per_device"]) == 4
    assert all(v > 0 for v in rows["rows_dispatched_per_device"].values())
    assert out.returncode != 0
    assert json.loads(out.stdout.strip().splitlines()[-1]) == {
        "ok": False,
        "device": {"platform": "cpu", "kind": "cpu", "count": 4}}


def test_without_rehearse_a_cpu_ends_the_run_before_any_work():
    out, lines = _run("--seed", "5", timeout=300)
    assert out.returncode != 0
    assert [doc["phase"] for doc in lines if doc.get("summary")] == ["bulk"]
    assert not any("suite" in doc for doc in lines)
    assert json.loads(out.stdout.strip().splitlines()[-1])["ok"] is False


def test_a_phase_whose_kernel_raises_fails_the_phase():
    """No phase's failure is caught and passed over: break the count
    kernel's builder and the visibility phase must not report."""
    broken = textwrap.dedent("""
        import sys
        sys.path.insert(0, %r)
        import chip_smoke
        from cadence_tpu.ops import scan
        def refuse(plan):
            raise RuntimeError("kernel refused")
        scan.build_count = refuse
        sys.exit(chip_smoke.main(["--phase", "visibility", "--rehearse"]))
    """ % REPO)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    out = subprocess.run([sys.executable, "-c", broken], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "kernel refused" in out.stderr
    assert not any('"failures"' in line for line in out.stdout.splitlines())
