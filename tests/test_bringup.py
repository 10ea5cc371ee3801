"""What running on a chip relies on, checked on the CPU: the compile
cache goes where ``JAX_COMPILATION_CACHE_DIR`` says (or to one fixed
path in the checkout) and a second run adds nothing to it; the benchmark
harness never initialises a JAX backend in its own process;
``chip_smoke.py`` refuses to run without a TPU; and one seed gives the
same weights in every process."""
import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code, timeout=300, **env):
    full = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
                JAX_PLATFORMS="cpu")
    full.pop("JAX_COMPILATION_CACHE_DIR", None)
    full.update(env)
    return subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          env=full, capture_output=True, text=True,
                          timeout=timeout, cwd=ROOT)


def test_compile_cache_default_is_fixed_path_in_checkout():
    r = _run("""
        import jax
        from repro.launch.compile_cache import DEFAULT_DIR, enable_compile_cache
        print(enable_compile_cache())
        print(jax.config.jax_compilation_cache_dir)
        print(DEFAULT_DIR)
    """)
    assert r.returncode == 0, r.stderr[-2000:]
    returned, configured, default = r.stdout.split()
    assert returned == configured == default
    assert default == os.path.join(ROOT, ".jax_compile_cache")


def test_compile_cache_env_dir_holds_every_entry(tmp_path):
    code = """
        import jax, jax.numpy as jnp
        from repro.launch.compile_cache import enable_compile_cache
        print(enable_compile_cache())
        jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()
    """
    cache = tmp_path / "cache"
    first = _run(code, JAX_COMPILATION_CACHE_DIR=str(cache))
    assert first.returncode == 0, first.stderr[-2000:]
    assert first.stdout.split() == [str(cache)]
    entries = sorted(os.listdir(cache))
    assert entries, "no cache entry written"
    second = _run(code, JAX_COMPILATION_CACHE_DIR=str(cache))
    assert second.returncode == 0, second.stderr[-2000:]
    assert sorted(os.listdir(cache)) == entries


def test_bench_harness_parent_never_initialises_jax(tmp_path):
    """Every section and the invariant checks run in the harness process
    with its child processes stubbed out; JAX must still have no backend
    afterwards."""
    r = _run(f"""
        import importlib.util, os
        spec = importlib.util.spec_from_file_location(
            "bench_run", os.path.join("benchmarks", "run.py"))
        run = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(run)
        run.TUNED_PATH = {str(tmp_path / "tuned.json")!r}
        spawned = []
        run._spawn = lambda section, cmd, args: spawned.append(cmd)
        for fn in run.SECTIONS.values():
            fn()
        run.check_invariants()
        from jax._src import xla_bridge
        assert not xla_bridge.backends_are_initialized()
        children = {{c[-1] for c in spawned if "--child" in c}}
        assert children == {{"serve_traffic", "throughput"}}, children
        print("OK", len(spawned))
    """, timeout=600, BENCH_SMOKE="1")
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert r.stdout.split()[-2] == "OK"


def test_chip_smoke_refuses_cpu():
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300, cwd=ROOT)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs a TPU" in r.stderr


def test_init_params_same_weights_in_every_process():
    """Python salts ``hash`` per process (PYTHONHASHSEED); the per-leaf
    init keys must not depend on it."""
    code = """
        import dataclasses, jax, numpy as np
        from repro.configs import get_config
        from repro.models import init_params, model_specs
        cfg = dataclasses.replace(get_config("granite-3-2b").reduced(),
                                  num_layers=2, d_model=64, d_ff=128,
                                  vocab_size=256)
        params = init_params(model_specs(cfg), jax.random.PRNGKey(0))
        leaves = [np.asarray(x, np.float64) for x in jax.tree.leaves(params)]
        print(repr([float((x * np.arange(x.size).reshape(x.shape)).sum())
                    for x in leaves]))
    """
    outs = []
    for seed in ("0", "1"):
        r = _run(code, PYTHONHASHSEED=seed)
        assert r.returncode == 0, r.stderr[-2000:]
        outs.append(r.stdout.strip())
    assert outs[0] == outs[1]
