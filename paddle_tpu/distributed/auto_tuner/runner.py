"""Trial launcher: run one candidate config as an isolated subprocess.

Reference analog: the auto-tuner handing each candidate to the distributed
launcher and reading metrics back from logs
(python/paddle/distributed/auto_tuner/utils.py: gen_new_args /
read_metric_log). TPU-native: the subprocess bootstraps a virtual CPU mesh
of ``num_devices`` (exactly like ``__graft_entry__.dryrun_multichip``), so
the full dp×mp×pp×sharding search space is explorable on a single host.

One process per chip: trials run one at a time and this parent never
touches JAX, so with ``use_real_devices`` the one live trial is the only
process that wants the chips. A tuner embedded in a program that already
holds them must leave ``use_real_devices`` off (CPU trials).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import Dict, Optional

__all__ = ["run_trial"]


def run_trial(cur_cfg: Dict, tuner_cfg: Dict,
              timeout: Optional[float] = None) -> Dict:
    """Run one candidate; returns the trial's metric record (merged over
    the candidate dict). ``run_cmd`` in tuner_cfg overrides the built-in
    trial module (it must print one JSON line on stdout)."""
    from .utils import num_devices

    n = num_devices(tuner_cfg)
    trial = dict(cur_cfg)
    trial["model_cfg"] = tuner_cfg.get("model_cfg", {})
    trial["steps"] = tuner_cfg.get("steps_per_trial", 3)

    env = dict(os.environ)
    env["PADDLE_AUTO_TUNER_TRIAL"] = json.dumps(trial)

    # real devices only on explicit request: probing jax.devices() here
    # would initialize (and hold) the accelerator runtime in the tuner
    # parent, locking the chips away from every trial subprocess
    use_real = bool(tuner_cfg.get("use_real_devices", False))
    if not use_real:
        env["JAX_PLATFORMS"] = "cpu"
        env["PADDLE_AUTO_TUNER_FORCE_CPU"] = "1"
        flags = " ".join(
            f for f in env.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f)
        env["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}").strip()

    cmd = tuner_cfg.get("run_cmd") or [
        sys.executable, "-m", "paddle_tpu.distributed.auto_tuner.trial"]
    timeout = timeout or tuner_cfg.get("trial_timeout", 600)
    try:
        proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                              timeout=timeout)
    except subprocess.TimeoutExpired:
        return {**cur_cfg, "error": "timeout"}

    rec = None
    for line in reversed(proc.stdout.strip().splitlines()):
        try:
            rec = json.loads(line)
            break
        except (json.JSONDecodeError, ValueError):
            continue
    if rec is None:
        err = (proc.stderr or "")[-400:]
        kind = ("oom" if ("RESOURCE_EXHAUSTED" in err or
                          "Out of memory" in err) else "error")
        rec = {"error": kind, "detail": err}
    return {**cur_cfg, **rec}
