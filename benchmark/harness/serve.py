"""The system under test: one ``kyverno_tpu serve`` child, driven over HTTP.

This process never imports JAX: the child is the one process that holds
the chip. It is started through ``benchmark/serve_child.py``, which runs
the program's own CLI entry in-process and, once serve has stopped,
records the device's peak memory.
"""

from __future__ import annotations

import http.client
import itertools
import json
import os
import signal
import socket
import subprocess
import sys
import time
from typing import Dict, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CHILD = os.path.join(ROOT, "benchmark", "serve_child.py")


class HarnessError(Exception):
    pass


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# serve speaks HTTP/1.0, so every call is a new connection. Thousands a
# second from one source address reuse (address, port) pairs still in
# TIME_WAIT, and a dropped SYN then stalls a call for seconds; spreading
# the calls over the loopback network's source addresses avoids that.
_SOURCES = itertools.cycle([f"127.0.0.{i}" for i in range(2, 255)])


def call(port: int, method: str, path: str, body=None,
         timeout: float = 60.0) -> Tuple[int, bytes]:
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout,
                                      source_address=(next(_SOURCES), 0))
    try:
        if body is not None and not isinstance(body, bytes):
            body = json.dumps(body).encode()
        conn.request(method, path, body, {"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def metric(text: str, name: str, **labels) -> float:
    """Sum of every series of ``name`` whose labels include ``labels``."""
    total = 0.0
    for line in text.splitlines():
        if not line.startswith(name):
            continue
        rest = line[len(name):]
        if rest and rest[0] not in ("{", " "):
            continue
        if all(f'{k}="{v}"' in rest for k, v in labels.items()):
            total += float(line.split(" # ")[0].rsplit(" ", 1)[-1])
    return total


class Serve:
    """One serve child on free ports; ``stop()`` ends it and waits."""

    def __init__(self, policy_dir: str, serve_args, workdir: str,
                 fault: Optional[str] = None):
        self.adm_port, self.met_port = free_port(), free_port()
        self.workdir = workdir
        self.peak_file = os.path.join(workdir, "device_peak.json")
        self.log_path = os.path.join(workdir, "serve.log")
        cmd = [sys.executable, CHILD, "--peak-file", self.peak_file]
        if fault:
            cmd += ["--fault", fault]
        cmd += ["--", policy_dir, "--port", str(self.adm_port),
                "--metrics-port", str(self.met_port),
                "--log-file", os.path.join(workdir, "oplog.jsonl"),
                *serve_args]
        self._log = open(self.log_path, "wb")
        self.t_spawn = time.perf_counter()
        self.child = subprocess.Popen(cmd, cwd=ROOT, stdout=self._log,
                                      stderr=subprocess.STDOUT,
                                      start_new_session=True)

    def get(self, path: str, timeout: float = 120.0):
        status, data = call(self.met_port, "GET", path, timeout=timeout)
        if status != 200:
            raise HarnessError(f"GET {path} -> {status}: {data[:300]!r}")
        return json.loads(data) if data[:1] in (b"{", b"[") else data

    def metrics_text(self) -> str:
        return self.get("/metrics").decode()

    def wait_for(self, path: str, deadline_s: float) -> None:
        deadline = time.perf_counter() + deadline_s
        while True:
            if self.child.poll() is not None:
                raise HarnessError(f"serve exited with {self.child.returncode} "
                                   f"before {path} answered")
            try:
                status, _ = call(self.met_port, "GET", path, timeout=30)
                if status == 200:
                    return
            except (OSError, http.client.HTTPException):
                pass
            if time.perf_counter() > deadline:
                raise HarnessError(f"{path} did not answer 200 in {deadline_s} s")
            time.sleep(0.2)

    def cpu_seconds(self) -> float:
        """User plus system CPU seconds serve has used so far."""
        try:
            with open(f"/proc/{self.child.pid}/stat") as f:
                fields = f.read().rsplit(")", 1)[1].split()
            return (int(fields[11]) + int(fields[12])) / os.sysconf("SC_CLK_TCK")
        except (OSError, IndexError, ValueError):
            return 0.0

    def stop(self) -> Dict:
        """Stop serve, wait for it, and return what the child recorded of
        the device (``{}`` where it recorded nothing)."""
        if self.child.poll() is None:
            os.killpg(self.child.pid, signal.SIGTERM)
            try:
                self.child.wait(timeout=60)
            except subprocess.TimeoutExpired:
                os.killpg(self.child.pid, signal.SIGKILL)
                self.child.wait()
        self._log.close()
        try:
            with open(self.peak_file) as f:
                return json.load(f)
        except (OSError, ValueError):
            return {}
