"""The launcher with the timed path broken underneath: every /query
group-by answer has one average altered where it is produced (the
server's response shaping).  benchmark/tests/test_rehearsal.py drives a
whole run over it and must see `correct` come out false."""

from __future__ import annotations

import sys

from benchmark.harness import launcher


def _break_answers() -> None:
    from horaedb_tpu.server import main as server_main

    sound = server_main._downsample_json

    def altered(out: dict) -> dict:
        body = sound(out)
        avg = body.get("aggs", {}).get("avg")
        if avg and avg[0] and avg[0][0] is not None:
            avg[0][0] *= 1.001
        return body

    server_main._downsample_json = altered


if __name__ == "__main__":
    _break_answers()
    sys.exit(launcher.main())
