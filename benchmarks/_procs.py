"""Shared subprocess machinery for benches and the FT harness: managed
CLI processes with log capture + wait-for-pattern readiness (the
reference's ManagedProcess, tests/utils/managed_process.py:69)."""

from __future__ import annotations

import os
import re
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: child env: repo on PYTHONPATH (prepended, not defaulted) + CPU platform
#: unless the caller wants the TPU
ENV = dict(
    os.environ,
    PYTHONPATH=REPO + (
        os.pathsep + os.environ["PYTHONPATH"]
        if os.environ.get("PYTHONPATH") else ""
    ),
)


class ManagedProc:
    """Subprocess with a log file and wait-for-pattern readiness."""

    def __init__(self, name: str, argv: list[str], env: dict | None = None):
        self.name = name
        self.log_path = tempfile.NamedTemporaryFile(
            mode="w", suffix=f"-{name}.log", delete=False
        ).name
        self._log = open(self.log_path, "w")
        self.proc = subprocess.Popen(
            argv, cwd=REPO, env=env or ENV,
            stdout=self._log, stderr=subprocess.STDOUT,
        )

    def wait_for(self, pattern: str, timeout: float = 30.0,
                 peers: "list[ManagedProc] | None" = None) -> None:
        """Wait until the log matches. Fails fast if this process — or any
        of `peers` (e.g. the rest of a cluster this one depends on) —
        exits first, dumping the dead process's log."""
        rx = re.compile(pattern)
        deadline = time.time() + timeout
        while time.time() < deadline:
            with open(self.log_path) as f:
                if rx.search(f.read()):
                    return
            for p in (self, *(peers or ())):
                if p.proc.poll() is not None:
                    raise AssertionError(
                        f"{p.name} exited {p.proc.returncode} while "
                        f"waiting for {pattern!r} from {self.name}:\n"
                        + open(p.log_path).read()
                    )
            time.sleep(0.2)
        raise AssertionError(
            f"{self.name}: {pattern!r} not seen in {timeout}s:\n"
            + open(self.log_path).read()
        )

    def kill(self, sig=signal.SIGKILL) -> None:
        if self.proc.poll() is None:
            self.proc.send_signal(sig)
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                # escalate instead of raising: a raise here would skip
                # the caller's remaining stop() calls and leak processes
                if sig != signal.SIGKILL:
                    self.proc.kill()
                try:
                    self.proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    # D-state zombie: nothing more a signal can do —
                    # report it rather than abort the caller's remaining
                    # cleanup
                    print(f"[{self.name}] survived SIGKILL "
                          f"(pid {self.proc.pid})", file=sys.stderr)

    def stop(self) -> None:
        self.kill(signal.SIGTERM)
        self._log.close()


def cli(*args: str) -> list[str]:
    return [sys.executable, "-m", "dynamo_tpu.cli.run", *args]


def free_port() -> int:
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def pct(values, q: float):
    """Nearest-rank percentile (q in [0,1]); None on empty input. The one
    shared implementation for every bench's TTFT/latency tables."""
    if not values:
        return None
    v = sorted(values)
    return v[min(len(v) - 1, int(round(q * (len(v) - 1))))]
