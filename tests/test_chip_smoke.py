"""The chip path's gates, as far as a CPU box can hold them: where the
compile cache lives, that chip_smoke.py runs end to end as a dry run and
refuses to pass without the platform it was asked for, and that the
removed CPU re-exec cannot grow back."""

import importlib.util
import json
import os
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent

_CACHE_PROBE = (
    "import jax\n"
    "from horaedb_tpu.utils.compile_cache import enable_compile_cache\n"
    "before = jax.config.jax_compilation_cache_dir\n"
    "out = enable_compile_cache()\n"
    "import json\n"
    "print(json.dumps([before, jax.config.jax_compilation_cache_dir, out]))\n")


def _probe(cwd, **env) -> list:
    """enable_compile_cache() in a fresh process (its state is
    process-global): [config dir before, config dir after, returned]."""
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(PYTHONPATH=str(REPO), JAX_PLATFORMS="cpu",
                HORAEDB_COMPILE_CACHE="1", **env)
    out = subprocess.run([sys.executable, "-c", _CACHE_PROBE], cwd=cwd,
                         env=full, capture_output=True, text=True,
                         timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_compile_cache_leaves_a_placed_cache_alone(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: jax reads it itself and
    enable_compile_cache() must not touch jax_compilation_cache_dir."""
    placed = str(tmp_path / "placed")
    before, after, returned = _probe(tmp_path,
                                     JAX_COMPILATION_CACHE_DIR=placed)
    assert before == after == returned == placed
    assert not os.path.exists(placed)  # nor create it: jax owns it


def test_compile_cache_default_is_one_fixed_in_checkout_dir(tmp_path):
    """Unset: the fixed, git-ignored directory inside the checkout —
    the same path from two processes started in different places."""
    want = str(REPO / ".jax_cache")
    (tmp_path / "elsewhere").mkdir()
    a = _probe(tmp_path)
    b = _probe(tmp_path / "elsewhere")
    assert a == b == [None, want, want]
    ignored = (REPO / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored


def _smoke(tmp_path, *args) -> subprocess.CompletedProcess:
    # conftest's 8-virtual-device XLA_FLAGS must not leak into the
    # server children: the smoke sets what its legs need itself
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["TMPDIR"] = str(tmp_path)
    return subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), "--rows", "200000",
         "--repeats", "1", "--out", str(tmp_path / "out"), *args],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)


def test_chip_smoke_dry_run_completes(tmp_path):
    """`--platform cpu --rows 200000`: ingest over HTTP with read-back,
    the five query shapes against the numpy reference, the restart leg
    against the compile cache — and the driving process never imports
    jax (asserted by the script from its own sys.modules before it
    prints the result)."""
    out = _smoke(tmp_path, "--platform", "cpu")
    assert out.returncode == 0, (out.stdout[-3000:], out.stderr[-3000:])
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {"platform": "cpu",
                                           "kind": "cpu", "count": 1}}
    summary = json.loads((tmp_path / "out" / "summary.json").read_text())
    assert summary["ok"] is True and summary["rows"] == 200_000
    assert list(summary)[-1] == "claim" and summary["claim"] is None
    steps = {s["step"] for s in summary["steps"]}
    assert {"ingest", "query.full.cold", "query.sub.cold", "query.point",
            "query.topk", "query.raw", "restart"} <= steps
    # the data directory lived in TMPDIR and is gone
    assert not list(tmp_path.glob("horaedb-chip-smoke-*"))


def test_chip_smoke_fails_without_the_chip(tmp_path):
    """The default `--platform tpu` on a box whose jax runs on the CPU:
    non-zero exit and no result line."""
    out = _smoke(tmp_path)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "expected 'tpu'" in out.stderr


def test_lint_rejects_reexec_and_retired_plugin_name(tmp_path):
    spec = importlib.util.spec_from_file_location(
        "lint_under_test", REPO / "tools" / "lint.py")
    lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lint)

    reexec = ("import os\nimport sys\n\n\n"
              "def fallback(env):\n"
              "    os.exec" "ve(sys.executable, sys.argv, env)\n")
    plugin = 'POOL = "PALLAS_' + lint._PLUGIN_NAME.upper() + '_POOL_IPS"\n'
    # the name inside a longer word is not the plug-in
    fine = f"WORD = 't{lint._PLUGIN_NAME}omy'\n"
    for rel in ("horaedb_tpu/x.py", "tools/x.py", "chip_smoke.py"):
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(reexec)
        assert any("re-exec" in p for p in lint.lint_file(path)), rel
        path.write_text(plugin)
        assert any("plug-in" in p for p in lint.lint_file(path)), rel
        path.write_text(fine)
        assert not lint.lint_file(path), rel
    # outside the scope (tests may spell both out)
    other = tmp_path / "tests" / "x.py"
    other.parent.mkdir()
    other.write_text(reexec)
    assert not lint.lint_file(other)
