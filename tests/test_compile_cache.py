"""``enable_compile_cache``: an explicit directory wins, the repo's own is
the default, and the campaign parent, which compiles nothing, stays free
of jax."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

# what the campaign parent does before it spawns its cell children
CODE = """
import sys
from repro.campaign import expand_campaign, get_campaign
from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache
expand_campaign(get_campaign("ci"))
path = enable_compile_cache()
assert "jax" not in sys.modules, "the campaign parent imported jax"
import jax
assert jax.config.jax_compilation_cache_dir == path, path
print(path == str(REPO_CACHE_DIR), path)
"""


@pytest.mark.parametrize("given", [None, "explicit"])
def test_compile_cache_dir(tmp_path, given):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    if given:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    r = subprocess.run([sys.executable, "-c", CODE], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    is_repo, path = r.stdout.split()
    assert is_repo == str(given is None)
    assert path == (str(tmp_path) if given else str(ROOT / ".jax_cache"))
