"""Driver-side handle on the server child (benchmark/harness/launcher.py)
and the few HTTP helpers set-up and the counter reads use.  Nothing
here imports jax: the child is the one holder of the chip."""

from __future__ import annotations

import http.client
import json
import os
import re
import signal
import socket
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# set-up requests may include XLA compiles (tens of seconds on a cold
# cache): they ask for the longest deadline the server grants
# ([admission] max_timeout).  The timed clients send no such header.
SETUP_HEADERS = {"X-Deadline-Ms": "300000"}


class BenchError(Exception):
    """The run cannot produce a result (no chip, server died, a set-up
    step failed): exit non-zero, print no result line."""


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def render_config(base_text: str, port: int, data_dir: str,
                  overrides: dict) -> str:
    """The base TOML (docs/example.toml) with `port` and `data_dir`
    changed, plus the configuration file's own `section.key = value`
    overrides (none in the two TSBS deployments)."""

    def set_key(text: str, section: str, key: str, value: str) -> str:
        head = re.escape(f"[{section}]") if section else r"\A"
        pat = re.compile(
            rf"({head}(?:(?!^\[).)*?^{re.escape(key)} = )[^\n#]*",
            re.S | re.M)
        out, n = pat.subn(lambda m: m.group(1) + value, text, count=1)
        if n != 1:
            raise BenchError(f"server config: no `{key}` under [{section}]")
        return out

    text = set_key(base_text, "", "port", str(port))
    text = set_key(text, "metric_engine.object_store", "data_dir",
                   json.dumps(data_dir))
    for dotted, value in overrides.items():
        section, _, key = dotted.rpartition(".")
        text = set_key(text, section, key,
                       value if isinstance(value, str) else json.dumps(value))
    return text


class Server:
    def __init__(self, out_dir: str, data_dir: str, config: dict,
                 platform: str, env_extra: dict | None = None,
                 launcher: str = "benchmark.harness.launcher"):
        self.port = free_port()
        base = os.path.join(ROOT, config["server"]["base"])
        with open(base, encoding="utf-8") as f:
            text = render_config(f.read(), self.port, data_dir,
                                 config["server"].get("overrides", {}))
        self.cfg_path = os.path.join(out_dir, "server.toml")
        with open(self.cfg_path, "w", encoding="utf-8") as f:
            f.write(text)
        self.log_path = os.path.join(out_dir, "server.log")
        self.log = open(self.log_path, "w", encoding="utf-8")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", launcher,
             "--config", self.cfg_path, "--platform", platform,
             "--chips", str(config["chips"])],
            cwd=ROOT, env=dict(os.environ, **(env_extra or {})),
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=self.log, text=True)

    # ---- control channel --------------------------------------------------

    def control(self, **msg) -> dict:
        self.proc.stdin.write(json.dumps(msg) + "\n")
        self.proc.stdin.flush()
        while True:
            line = self.proc.stdout.readline()
            if not line:
                raise BenchError(
                    f"server exited rc={self.proc.poll()} during "
                    f"{msg.get('cmd')}\n{self.tail()}")
            if line.startswith("@@ctl "):
                out = json.loads(line[6:])
                if not out.get("ok"):
                    raise BenchError(f"control {msg}: {out.get('error')}")
                return out

    def device(self) -> dict:
        """The device as the child's JAX reports it, with the peak
        bytes in use on the fullest chip."""
        out = self.control(cmd="device")
        del out["ok"]
        return out

    # ---- HTTP -------------------------------------------------------------

    def request(self, method: str, path: str, body=None,
                timeout: float = 600.0) -> bytes:
        headers = dict(SETUP_HEADERS)
        if isinstance(body, (dict, list)):
            body = json.dumps(body).encode()
            headers["Content-Type"] = "application/json"
        conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=timeout)
        try:
            conn.request(method, path, body=body, headers=headers)
            resp = conn.getresponse()
            data = resp.read()
        finally:
            conn.close()
        if resp.status != 200:
            raise BenchError(f"{method} {path.split('?')[0]} -> "
                             f"{resp.status} {data[:300]!r}")
        return data

    def get_json(self, path: str) -> dict:
        return json.loads(self.request("GET", path))

    def wait_ready(self, timeout: float = 600.0) -> None:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            rc = self.proc.poll()
            if rc is not None:
                raise BenchError(
                    f"server exited rc={rc} before listening (rc 3: no "
                    f"such platform or too few chips)\n{self.tail()}")
            try:
                self.request("GET", "/", timeout=2.0)
                return
            except (OSError, http.client.HTTPException, BenchError):
                time.sleep(0.1)
        raise BenchError(f"server not listening after {timeout}s")

    def tail(self, n: int = 3000) -> str:
        self.log.flush()
        with open(self.log_path, encoding="utf-8", errors="replace") as f:
            return f.read()[-n:]

    def stop(self) -> None:
        """Graceful stop, then wait: the chip is free, and no process
        is left behind, only once the child is gone."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGINT)
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=30)
        for stream in (self.proc.stdin, self.proc.stdout):
            if stream is not None:
                stream.close()
        self.log.close()
