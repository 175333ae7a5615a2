"""Where the persistent compile cache goes (spark_rapids_tpu/__init__.py),
and that chip_smoke.py refuses a machine without a chip. Each case is a
fresh subprocess on the CPU: the package decides the place at import."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PRINT_DIR = ("import jax, spark_rapids_tpu; "
              "print(jax.config.jax_compilation_cache_dir)")


def _run(argv, **env_over):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "SRTPU_COMPILE_CACHE")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, **env_over)
    return subprocess.run([sys.executable] + argv, env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=120)


@pytest.mark.parametrize("placed", [True, False],
                         ids=["env_places_it", "checkout_default"])
def test_compile_cache_dir(tmp_path, placed):
    """JAX_COMPILATION_CACHE_DIR set: the package sets no directory of
    its own, so JAX's stays equal to it. Unset: <checkout>/.jax_cache."""
    want = str(tmp_path / "cc") if placed else os.path.join(REPO,
                                                            ".jax_cache")
    # the repo's own variable no longer places the cache, whatever it says
    env = {"SRTPU_COMPILE_CACHE": str(tmp_path / "ignored")}
    if placed:
        env["JAX_COMPILATION_CACHE_DIR"] = want
    r = _run(["-c", _PRINT_DIR], **env)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.strip().splitlines()[-1] == want
    assert not (tmp_path / "ignored").exists()


def test_chip_smoke_without_a_chip_fails_at_once():
    """No --rehearse on the CPU: non-zero exit, no data generated, and
    the last line of stdout says "ok": false with the device it found."""
    r = _run([os.path.join(REPO, "chip_smoke.py")])
    assert r.returncode != 0
    lines = r.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["ok"] is False and last["device"]["platform"] == "cpu"
    assert not any('"generate"' in ln for ln in lines)
