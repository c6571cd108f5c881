"""Shared spawn-with-budget harness for children that hold the chip
(exp_dots variants, autotune-sweep trials).

One implementation on purpose. A chip belongs to one process at a time,
so three failure modes matter: a child that hangs mid-compile, a killed
parent orphaning its child (which then keeps the chip from every later
process), and SIGKILL-only cleanup that skips child reaping.  The rules
encoded here:

- the child runs in its OWN session (``start_new_session=True``) so the
  whole process tree can be killed as a group;
- on budget expiry the group gets SIGTERM, a grace period to reap its
  own children, then SIGKILL;
- while the child runs, this process forwards an incoming SIGTERM to
  the child group before dying, so an OUTER timeout can never orphan
  the tree;
- partial stdout/stderr is salvaged on every path — it is the only
  evidence of where a hang happened.
"""
from __future__ import annotations

import os
import signal
import subprocess
import time
from typing import List, NamedTuple


class BudgetResult(NamedTuple):
    out: str
    err: str
    returncode: int  # -9 when group-killed
    timed_out: bool


def _killpg(pid: int, sig: int) -> None:
    try:
        os.killpg(pid, sig)
    except (ProcessLookupError, PermissionError):
        pass


def _term_then_kill(pid: int, grace: float = 10.0) -> None:
    """SIGTERM the group, give it ``grace`` seconds to reap its own
    children (a trapped TERM is how the bench watchdog kills ITS
    detached child), then SIGKILL.  Liveness is probed with signal 0 —
    never ``waitpid``, which would steal the exit status from the Popen
    that owns the child (a lingering zombie just burns the grace)."""
    _killpg(pid, signal.SIGTERM)
    deadline = time.monotonic() + grace
    while time.monotonic() < deadline:
        try:
            os.kill(pid, 0)
        except ProcessLookupError:
            return
        time.sleep(0.2)
    _killpg(pid, signal.SIGKILL)


def run_budgeted(cmd: List[str], budget: float,
                 env: dict = None) -> BudgetResult:
    """Run ``cmd`` in its own session with a wall-clock budget; never
    orphan its process tree, even when this process is SIGTERMed."""
    p = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                         stderr=subprocess.PIPE, text=True,
                         start_new_session=True)

    def _forward(signum, frame, _pid=p.pid):
        _term_then_kill(_pid, grace=5.0)
        raise SystemExit(128 + signum)

    prev = signal.signal(signal.SIGTERM, _forward)
    timed_out = False
    try:
        try:
            out, err = p.communicate(timeout=budget)
        except subprocess.TimeoutExpired:
            timed_out = True
            _term_then_kill(p.pid)
            out, err = p.communicate()  # partial buffers — the evidence
    except BaseException:  # Ctrl-C etc.: never orphan the claim
        _killpg(p.pid, signal.SIGKILL)
        raise
    finally:
        signal.signal(signal.SIGTERM, prev)
        if p.poll() is None:
            _killpg(p.pid, signal.SIGKILL)
    return BudgetResult(out or "", err or "", p.returncode, timed_out)
