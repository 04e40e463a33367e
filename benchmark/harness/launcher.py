"""The process that holds the chip: the program's own server
(`horaedb_tpu.server.main.run_server`) started in-process, so that the
same process can be traced with jax.profiler.

Started by benchmark/harness/server.py as
`python -m benchmark.harness.launcher --config <toml> --platform tpu
 --chips 1`.  It refuses to serve on any platform but the one asked for
(exit code 3, nothing on stdout): a run never falls back to the CPU.

Control: one JSON object per line on stdin, one `@@ctl {...}` line back
on stdout.  Commands: {"cmd": "device"} (platform, kind, count, peak
bytes on the fullest chip), {"cmd": "trace_start", "dir": ...},
{"cmd": "trace_stop"}.  The server's own log goes to stderr.  SIGINT
stops the server gracefully (engine closed, then exit 0).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
import sys
import threading


def _reply(obj: dict) -> None:
    sys.stdout.write("@@ctl " + json.dumps(obj) + "\n")
    sys.stdout.flush()


def _device_report() -> dict:
    import jax

    devices = jax.devices()
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "memory_peak_bytes": peak}


def _control_loop() -> None:
    import jax

    for line in sys.stdin:
        line = line.strip()
        if not line:
            continue
        try:
            msg = json.loads(line)
            cmd = msg["cmd"]
            if cmd == "device":
                _reply({"ok": True, **_device_report()})
            elif cmd == "trace_start":
                jax.profiler.start_trace(msg["dir"])
                _reply({"ok": True})
            elif cmd == "trace_stop":
                jax.profiler.stop_trace()
                _reply({"ok": True})
            else:
                _reply({"ok": False, "error": f"unknown cmd {cmd!r}"})
        except Exception as e:  # the boundary: report, keep serving
            _reply({"ok": False, "error": f"{type(e).__name__}: {e}"})


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--platform", required=True)
    ap.add_argument("--chips", type=int, required=True)
    args = ap.parse_args()
    logging.basicConfig(
        level=logging.INFO, stream=sys.stderr,
        format="%(asctime)s %(levelname)s %(name)s:%(lineno)d %(message)s")

    import jax

    devices = jax.devices()
    if devices[0].platform != args.platform:
        print(f"launcher: JAX runs on {devices[0].platform!r}, the cell "
              f"asks for {args.platform!r}", file=sys.stderr)
        return 3
    if args.platform != "cpu" and len(devices) < args.chips:
        print(f"launcher: {len(devices)} devices, the cell asks for "
              f"{args.chips}", file=sys.stderr)
        return 3

    from horaedb_tpu.server.config import load_config
    from horaedb_tpu.server.main import run_server

    threading.Thread(target=_control_loop, daemon=True,
                     name="bench-control").start()
    try:
        asyncio.run(run_server(load_config(args.config)))
    except KeyboardInterrupt:
        pass
    return 0


if __name__ == "__main__":
    sys.exit(main())
