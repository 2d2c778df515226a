"""Where the entry points keep JAX's persistent compilation cache.

``JAX_COMPILATION_CACHE_DIR`` is JAX's own setting and wins; without it
the cache goes to a fixed directory inside the checkout, so a second run
finds what the first compiled.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]

# one process per run: the cache is looked up when a program first
# compiles in a fresh process
_RUN = textwrap.dedent("""
    import os, sys
    from pathlib import Path
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    import jax, jax.numpy as jnp
    from repro.launch import compile_cache
    compile_cache.CACHE_DIR = Path(sys.argv[1])
    # cache even this tiny program (the defaults skip fast compiles)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    hits = []
    jax.monitoring.register_event_listener(
        lambda event, **kw: hits.append(event)
        if event == "/jax/compilation_cache/cache_hits" else None)
    where = compile_cache.use_compile_cache()
    jax.jit(lambda x: x * 2.0 + 1.0)(jnp.arange(8.0)).block_until_ready()
    print(where, len(hits))
""")


def test_environment_setting_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_a_fixed_ignored_directory_in_the_checkout():
    assert compile_cache.CACHE_DIR == ROOT / ".jax_cache"
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_second_run_hits_the_cache(tmp_path):
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"),
               JAX_PLATFORMS="cpu")

    def run():
        out = subprocess.run([sys.executable, "-c", _RUN, str(tmp_path)],
                             env=env, capture_output=True, text=True,
                             timeout=120)
        assert out.returncode == 0, out.stderr
        where, hits = out.stdout.split()[-2:]
        assert where == str(tmp_path)
        return int(hits)

    assert run() == 0
    assert any(tmp_path.iterdir())
    assert run() >= 1
