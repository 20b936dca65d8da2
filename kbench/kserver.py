"""Starting, probing and stopping the server child, over HTTP only.

The pattern is ``chip_smoke.py``'s (chip-proven in PR 21), copied and
not imported: a parent that never touches JAX, the server as a child
in its own session, ``/health`` polled until ok, ``/metrics`` scraped
as text, SIGTERM and a wait for exit code 0.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

from paths import KBENCH, ROOT


class BenchError(Exception):
    pass


def log(msg: str) -> None:
    print(f"[kbench] {msg}", file=sys.stderr, flush=True)


def child_env(extra: dict = None) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra or {})
    return env


def tail(path: str, n: int = 40) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return ""


def stop_child(proc: subprocess.Popen, grace_s: float = 60.0) -> int:
    """SIGTERM, wait, and leave nothing of the child's group behind."""
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=grace_s)
        except subprocess.TimeoutExpired:
            log(f"child {proc.pid} ignored SIGTERM for {grace_s:.0f}s")
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass
    return proc.wait()


def run_child(cmd: list, log_path: str, env: dict = None,
              timeout_s: float = 1000.0) -> int:
    """Run one child to its end in its own session; returns its code."""
    with open(log_path, "w") as out:
        proc = subprocess.Popen(cmd, cwd=ROOT, env=child_env(env), stdout=out,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
        try:
            proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            log(f"{cmd[1]} still running after {timeout_s:.0f}s")
        finally:
            stop_child(proc, grace_s=5.0)
    return proc.returncode


def build_native() -> None:
    """The prefix cache's library is not committed: build it here."""
    native = os.path.join(ROOT, "kaito_tpu", "native")
    if os.path.exists(os.path.join(native, "libkaito_native.so")):
        return
    res = subprocess.run(["make", "-C", native, "all"],
                         capture_output=True, text=True)
    if res.returncode != 0:
        raise BenchError(f"native build failed:\n{res.stdout}{res.stderr}")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def parse_metrics(text: str) -> dict:
    """Unlabelled samples of a /metrics page as {name: value}."""
    out = {}
    for line in text.splitlines():
        parts = line.split()
        if len(parts) == 2 and not line.startswith("#") and "{" not in line:
            try:
                out[parts[0]] = float(parts[1])
            except ValueError:
                pass
    return out


class Server:
    """One ``launch_server.py`` child."""

    def __init__(self, *, config_path: str, name: str, tokenizer_dir: str,
                 weight_seed: int, work_dir: str, env: dict = None):
        self.port = free_port()
        self.base = f"http://127.0.0.1:{self.port}"
        self.log_path = os.path.join(work_dir, "server.log")
        self.cmd = [sys.executable, os.path.join(KBENCH, "launch_server.py"),
                    "--config", config_path, "--name", name,
                    "--tokenizer-dir", tokenizer_dir,
                    "--weight-seed", str(weight_seed),
                    "--port", str(self.port), "--work-dir", work_dir]
        self.env = env or {}
        self.proc = None
        self.t_launch = 0.0

    def __enter__(self):
        self.t_launch = time.monotonic()
        with open(self.log_path, "w") as out:
            self.proc = subprocess.Popen(
                self.cmd, cwd=ROOT, env=child_env(self.env), stdout=out,
                stderr=subprocess.STDOUT, start_new_session=True)
        return self

    def __exit__(self, *exc):
        if self.proc.poll() is None:      # a failure path: just stop it
            stop_child(self.proc)
        return False

    def request(self, path: str, body=None, timeout: float = 600.0):
        """(status, bytes); connection errors raise OSError."""
        data = None if body is None else json.dumps(body).encode()
        req = urllib.request.Request(
            self.base + path, data=data,
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return r.status, r.read()
        except urllib.error.HTTPError as e:
            return e.code, e.read()

    def post_json(self, path: str, body: dict, timeout: float = 600.0) -> dict:
        status, raw = self.request(path, body, timeout)
        if status != 200:
            raise BenchError(f"{path} answered {status}: {raw[:400]!r}")
        return json.loads(raw)

    def wait_healthy(self, expect_platform: str,
                     timeout_s: float = 900.0) -> dict:
        """Poll /health until the engine is up.  The loading stub (503)
        already names the platform: the wrong one fails here, before
        the weights load."""
        while time.monotonic() - self.t_launch < timeout_s:
            if self.proc.poll() is not None:
                raise BenchError(
                    f"server exited {self.proc.returncode} while loading:\n"
                    + tail(self.log_path))
            try:
                status, body = self.request("/health", timeout=5.0)
            except OSError:
                status = 0
            if status in (200, 503):
                health = json.loads(body)
                if health.get("platform") != expect_platform:
                    raise BenchError(
                        f"server runs on {health.get('platform')!r}, "
                        f"expected {expect_platform!r}")
                if status == 200:
                    return health
            time.sleep(0.25)
        raise BenchError(f"server not healthy after {timeout_s:.0f}s:\n"
                         + tail(self.log_path))

    def health(self) -> dict:
        status, body = self.request("/health", timeout=30.0)
        if status != 200:
            raise BenchError(f"/health answered {status}")
        return json.loads(body)

    def metrics(self) -> dict:
        status, body = self.request("/metrics", timeout=30.0)
        if status != 200:
            raise BenchError(f"/metrics answered {status}")
        return parse_metrics(body.decode())

    def stop(self) -> None:
        rc = stop_child(self.proc)
        if rc != 0:
            raise BenchError(f"server exited {rc} on SIGTERM:\n"
                             + tail(self.log_path))
