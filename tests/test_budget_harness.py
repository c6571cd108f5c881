"""experiments/_budget.py — the spawn-with-budget harness that guards the
round record (bench watchdog) and the per-variant experiment isolation.

Reference analog: the reference's elastic/launch watchdogs
(fleet/launch/controller process management) kill worker process GROUPS
on timeout; this harness is the TPU-session equivalent and must never
orphan a child (an orphaned remote-compile helper holds the device claim
and wedges every later probe — observed 2026-07-31)."""
import os
import signal
import subprocess
import sys
import time

import pytest

# wall-clock-bound by design (children sleep out real timeout budgets):
# rides the slow tier (run with -m slow), not tier-1 — moved when the
# prefix-cache suite (round 11) pushed tier-1 against its 870s timeout
pytestmark = pytest.mark.slow

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "experiments"))

from _budget import run_budgeted  # noqa: E402


def test_fast_child_passes_through():
    # -I everywhere in children: isolated mode skips site and user
    # customisation, so interpreter startup cannot starve short test
    # budgets or make "what did the child print before the kill"
    # nondeterministic
    r = run_budgeted([sys.executable, "-I", "-c", "print('hello'); "
                      "import sys; print('err', file=sys.stderr)"], 30)
    assert r.out.strip() == "hello"
    assert r.err.strip() == "err"
    assert r.returncode == 0
    assert not r.timed_out


def test_timeout_kills_whole_group():
    # child spawns a SAME-GROUP grandchild (the usual helper shape: plain
    # Popen inherits the group) then hangs; the budget's killpg must take
    # both.  The other shape — a grandchild in its OWN session, reachable
    # only via its parent's TERM trap — is what
    # test_sigterm_forwarded_to_child_group exercises (run_budgeted's
    # child is session-detached by construction).
    code = (
        "import subprocess, sys, time\n"
        "p = subprocess.Popen([sys.executable, '-I', '-c', 'import time; "
        "time.sleep(120)'])\n"
        "print('GRANDCHILD', p.pid, flush=True)\n"
        "time.sleep(120)\n")
    t0 = time.monotonic()
    r = run_budgeted([sys.executable, "-I", "-u", "-c", code], 3)
    assert r.timed_out
    assert time.monotonic() - t0 < 60  # budget + grace, not 120s
    gpid = int(r.out.split()[1])  # partial stdout salvaged
    # grandchild must be dead (or a reaped zombie) — signal 0 probes
    for _ in range(50):
        try:
            os.kill(gpid, 0)
        except ProcessLookupError:
            break
        # still alive: only acceptable as a zombie awaiting init's reap
        try:
            stat = open(f"/proc/{gpid}/stat").read().split()[2]
        except FileNotFoundError:  # reaped between probes — dead: success
            break
        if stat == "Z":
            break
        time.sleep(0.2)
    else:
        raise AssertionError(f"grandchild {gpid} survived the group kill")


def test_partial_stdout_salvaged_on_timeout():
    r = run_budgeted([sys.executable, "-I", "-u", "-c",
                      "print('evidence'); import time; time.sleep(60)"], 2)
    assert r.timed_out
    assert "evidence" in r.out


def test_sigterm_forwarded_to_child_group(tmp_path):
    """Outer TERM to the HARNESS process must kill the child group before
    the harness dies (the runbook's step-timeout path). The child is
    tagged with a unique argv marker so its survival is observable."""
    marker = f"budget_harness_marker_{os.getpid()}"
    exp_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "experiments")
    child = tmp_path / "tagged_child.py"
    child.write_text(f"# {marker}\nimport time\ntime.sleep(120)\n")
    helper = tmp_path / "helper.py"
    helper.write_text("\n".join([
        "import sys",
        f"sys.path.insert(0, {exp_dir!r})",
        "from _budget import run_budgeted",
        f"run_budgeted([sys.executable, '-I', '-u', {str(child)!r},",
        f"              {marker!r}], 100)",
    ]))
    p = subprocess.Popen([sys.executable, "-I", "-u", str(helper)])
    time.sleep(3)  # let the child start
    p.send_signal(signal.SIGTERM)
    rc = p.wait(timeout=30)
    assert rc in (128 + signal.SIGTERM, -signal.SIGTERM)
    # the tagged child must not survive its harness
    time.sleep(1)
    left = subprocess.run(["pgrep", "-f", marker],
                          capture_output=True, text=True)
    assert left.stdout.strip() == "", f"orphaned child: {left.stdout}"
