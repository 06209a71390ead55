"""The parent's side of a chip-owning child: spawn it in its own process
group, exchange JSON lines with it, and stop it for certain.

One process holds a chip at a time, so ``benchmarks.run`` (which drives the
load) never imports jax; the child calls ``ray_tpu.init`` and the replica or
train worker it starts owns the chip. Layout copied from ``chip_smoke.py``.
"""
from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import threading
import time

from .spec import ROOT


def child_env(rehearse: bool, chips: int) -> dict:
    env = dict(os.environ)
    from ray_tpu.util.compile_cache import CACHE_ENV, compile_cache_dir
    # a fixed directory inside the checkout unless the environment names
    # one: the path is part of the cache key
    env[CACHE_ENV] = compile_cache_dir()
    env["PYTHONPATH"] = os.pathsep.join(
        [ROOT] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
    env.setdefault("TPU_LOG_DIR", "disabled")
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        if chips > 1:
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "") + " --xla_force_host_platform"
                f"_device_count={chips}").strip()
    return env


class Child:
    def __init__(self, module: str, args: list, env: dict, log=print):
        self.log = log
        self.proc = subprocess.Popen(
            [sys.executable, "-m", module, *args], cwd=ROOT, env=env,
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            start_new_session=True)
        self.session_dirs: list = []

    def read(self, timeout: float, **match) -> dict:
        """The child's next JSON line that carries ``match``; other lines
        are passed to the log. Raises if the child ends or time runs out."""
        deadline = time.monotonic() + timeout
        while True:
            box: list = []
            t = threading.Thread(
                target=lambda: box.append(self.proc.stdout.readline()),
                daemon=True)
            t.start()
            t.join(max(deadline - time.monotonic(), 0.0))
            if not box:
                raise TimeoutError(f"no {match} from the child in time")
            line = box[0].strip()
            if not line:
                if self.proc.poll() is not None:
                    raise RuntimeError(
                        f"child ended (code {self.proc.returncode}) "
                        f"before {match}")
                continue
            if not line.startswith("{"):
                self.log(line)
                continue
            msg = json.loads(line)
            if "session_dir" in msg:
                self.session_dirs.append(msg["session_dir"])
            if msg.get("event") == "error":
                raise RuntimeError(f"child failed: {msg.get('error')}")
            if all(msg.get(k) == v for k, v in match.items()):
                return msg
            self.log(line)

    def ask(self, cmd: str, timeout: float = 120.0, **fields) -> dict:
        self.proc.stdin.write(json.dumps({"cmd": cmd, **fields}) + "\n")
        self.proc.stdin.flush()
        return self.read(timeout, event=cmd)

    def stop(self) -> None:
        """Close its stdin (the child then shuts down by itself), then
        signal the whole group, and wait until the child has ended."""
        try:
            self.proc.stdin.close()
        except OSError:
            pass
        for sig, patience in ((None, 30), (signal.SIGTERM, 15),
                              (signal.SIGKILL, None)):
            if sig is not None:
                try:
                    os.killpg(self.proc.pid, sig)
                except ProcessLookupError:
                    pass
            try:
                self.proc.wait(timeout=patience)
            except subprocess.TimeoutExpired:
                continue
        # the leader has ended: nothing of its group may outlive the run
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass

    def worker_log_tails(self, lines: int = 40) -> str:
        import glob
        out = []
        for sdir in self.session_dirs:
            for path in sorted(glob.glob(os.path.join(sdir, "worker-*.log"))):
                with open(path, errors="replace") as f:
                    out.append(f"--- {path}\n" + "".join(
                        f.readlines()[-lines:]))
        return "\n".join(out)

    def remove_session_dirs(self) -> None:
        import shutil
        for sdir in self.session_dirs:
            shutil.rmtree(sdir, ignore_errors=True)
