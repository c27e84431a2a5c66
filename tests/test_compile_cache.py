"""Process hygiene of the entry points: where the persistent compilation
cache goes (``repro.launch.compile_cache``), and that a supervising parent
starts its children without holding a backend. Each case runs in a fresh
interpreter, so nothing leaks into the test process's JAX state."""
import os
import subprocess
import sys
import textwrap

import pytest

from repro.launch.compile_cache import CHECKOUT_CACHE_DIR

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# compiles one uniquely named function; its entry is keyed by that name
_PROBE = textwrap.dedent("""
    import sys
    from repro.launch.compile_cache import enable_compile_cache
    got = enable_compile_cache()
    import jax, jax.numpy as jnp
    assert jax.config.jax_compilation_cache_dir == got, (
        jax.config.jax_compilation_cache_dir, got)
    def {name}(x):
        return jnp.sin(x) * 3.0
    jax.jit({name})(jnp.ones(3)).block_until_ready()
    print(got)
""")


def _run(code, **env_over):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=os.path.join(_ROOT, "src"),
               JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0", **env_over)
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-2000:]
    return res.stdout.strip().splitlines()[-1]


def _entries(d, name):
    return [f for f in os.listdir(d) if name in f] if os.path.isdir(d) else []


def test_env_cache_dir_is_left_to_jax(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: entries land there and nowhere else."""
    name = f"probe_env_{os.getpid()}"
    got = _run(_PROBE.format(name=name),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert got == str(tmp_path)
    assert _entries(str(tmp_path), name)
    assert not _entries(CHECKOUT_CACHE_DIR, name)


def test_default_cache_dir_is_fixed_under_the_checkout():
    """Unset: the cache is ``<checkout>/.jax_cache`` — a fixed path, so a
    later process finds what an earlier one compiled — and git ignores it."""
    assert CHECKOUT_CACHE_DIR == os.path.join(_ROOT, ".jax_cache")
    name = f"probe_default_{os.getpid()}"
    try:
        got = _run(_PROBE.format(name=name))
        assert got == CHECKOUT_CACHE_DIR
        assert _entries(CHECKOUT_CACHE_DIR, name)
    finally:
        for f in _entries(CHECKOUT_CACHE_DIR, name):
            os.remove(os.path.join(CHECKOUT_CACHE_DIR, f))
    with open(os.path.join(_ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_entries_are_keyed_by_named_scopes(tmp_path):
    """Two programs that differ only in a named scope get an entry each:
    the scopes live only in the ops' metadata, which the profiler reads
    from the compiled program, so neither may load the other's."""
    name = f"probe_scope_{os.getpid()}"
    code = textwrap.dedent(f"""
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        import jax, jax.numpy as jnp
        def {name}(x):
            with jax.named_scope(SCOPE):
                return jnp.sin(x) * 3.0
        jax.jit({name})(jnp.ones(3)).block_until_ready()
        print("ok")
    """)
    for scope in ("fed.a", "fed.b", "fed.a"):
        _run(f"SCOPE = {scope!r}" + code,
             JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert len(_entries(str(tmp_path), name)) == 2


@pytest.mark.timeout(300)
def test_supervising_parent_holds_no_backend(tmp_path):
    """``train --max-restarts`` only starts children: the parent never
    initialises a JAX backend, so on a chip each child is its one holder."""
    code = textwrap.dedent(f"""
        import subprocess
        from jax._src import xla_bridge
        from repro.launch import train
        calls = []
        subprocess.call = lambda cmd, **kw: calls.append(cmd) or 0
        train.main(["--max-restarts", "2", "--ckpt-dir", {str(tmp_path)!r},
                    "--steps", "1"])
        assert len(calls) == 1, calls
        assert not xla_bridge.backends_are_initialized()
        print("ok")
    """)
    assert _run(code) == "ok"
