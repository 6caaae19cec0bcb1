"""The two rules that keep the device and the compile cache honest:

  * the place rule (paddle_tpu/places.py): a TPUPlace with no TPU, or
    with no such chip, is an error — except in a process pinned to the
    CPU on purpose by JAX_PLATFORMS=cpu;
  * the cache rule (core/compile_cache.enable_persistent_cache): jax's
    persistent cache lives at JAX_COMPILATION_CACHE_DIR when that is set
    and nothing in the tree then repoints it; unset, it lives at
    <checkout>/.jax_cache, the same path in every process.
"""
import os
import re
import subprocess
import sys

import pytest

import paddle_tpu as fluid
from paddle_tpu import places

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


# ------------------------------------------------------------ place rule --
class TestCpuOnlyEnv:
    def test_cpu_only(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        assert places.cpu_only_env()

    def test_unset_is_not_cpu_only(self, monkeypatch):
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        assert not places.cpu_only_env()

    def test_accelerator_listed(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
        assert not places.cpu_only_env()


def test_tpuplace_on_explicit_cpu_is_the_first_cpu_device():
    import jax
    assert fluid.TPUPlace().device() == jax.devices("cpu")[0]
    assert fluid.CUDAPlace(0).device() == jax.devices("cpu")[0]


def test_tpuplace_without_a_tpu_raises(monkeypatch):
    """This host has no TPU; take away the explicit-CPU exemption and the
    place must refuse, not hand back the CPU."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(RuntimeError, match="no accelerator"):
        fluid.TPUPlace().device()
    with pytest.raises(SystemExit, match="refusing to emit CPU numbers"):
        places.require_accelerator("some_probe")


def test_tpuplace_out_of_range_raises_rather_than_wraps():
    with pytest.raises(ValueError, match=r"TPUPlace\(9\)"):
        fluid.TPUPlace(9).device()
    with pytest.raises(ValueError):
        fluid.TPUPlace(-1).device()


# ------------------------------------------------------------ cache rule --
_REPORT = """
import sys
sys.path.insert(0, %r)
import jax
updates = []
real_update = jax.config.update
def spy(name, value):
    updates.append(name)
    return real_update(name, value)
jax.config.update = spy
from paddle_tpu.core import compile_cache as cc
print(cc.enable_persistent_cache())
print(cc.enable_aot_cache())
print(jax.config.jax_compilation_cache_dir)
print(updates.count("jax_compilation_cache_dir"))
""" % REPO


def _report(env_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("FLAGS_aot_cache_dir", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _REPORT], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    return out.stdout.split()


def test_env_cache_dir_is_never_overruled(tmp_path):
    want = str(tmp_path / "from_env")
    xla_dir, aot_dir, in_effect, updates = _report(want)
    assert xla_dir == in_effect == want
    assert aot_dir == os.path.join(want, "aot")
    assert updates == "0"   # jax read the variable itself; we set nothing


def test_default_cache_dir_is_the_checkout_in_every_process():
    first = _report(None)
    second = _report(None)
    assert first == second
    xla_dir, aot_dir, in_effect, updates = first
    assert xla_dir == in_effect == os.path.join(REPO, ".jax_cache")
    assert aot_dir == os.path.join(REPO, ".jax_cache", "aot")
    assert updates == "1"


def test_one_cache_dir_update_in_the_tree_and_no_temp_paths():
    """Source guard: one `config.update("jax_compilation_cache_dir", ...)`
    in paddle_tpu, chip_smoke.py and tools — in the deciding
    function — and core/compile_cache.py builds no path from tempfile."""
    files = [os.path.join(REPO, "chip_smoke.py")]
    for top in ("paddle_tpu", "tools"):
        for root, _, names in os.walk(os.path.join(REPO, top)):
            files += [os.path.join(root, n) for n in names
                      if n.endswith(".py")]
    hits = []
    for path in files:
        with open(path) as f:
            src = f.read()
        hits += [path] * len(re.findall(
            r'config\.update\(\s*"jax_compilation_cache_dir"', src))
    assert hits == [os.path.join(REPO, "paddle_tpu", "core",
                                 "compile_cache.py")], hits
    with open(hits[0]) as f:
        assert "tempfile" not in f.read()


# ----------------------------------------------- what rides on the rules --
def test_pool_round_robins_over_the_chips_itself(monkeypatch):
    """TPUPlace no longer wraps device ids, so the pool does the modulo:
    replica idx lands on chip idx % n, and never out of range."""
    from paddle_tpu.serving.pool import ReplicaPool

    pool = ReplicaPool.__new__(ReplicaPool)     # placement logic only
    pool._place, pool.tp = None, None
    chips = ["chip0", "chip1", "chip2", "chip3"]
    monkeypatch.setattr(fluid.TPUPlace, "devices",
                        staticmethod(lambda: chips))
    assert [pool._place_for(i).device() for i in range(6)] \
        == chips + chips[:2]
    explicit = fluid.CPUPlace()
    pool._place = explicit
    assert pool._place_for(5) is explicit


def test_require_accelerator_admits_the_explicit_cpu():
    import jax
    assert places.require_accelerator("probe") == jax.devices()[0]


def test_native_libraries_load_through_make(monkeypatch):
    """load_library always goes through `make` (a no-op when current), so
    the git-ignored .so lying in the directory is whatever the committed
    sources build — and when the build fails the library is NOT loaded,
    even if a file is there; native_status says what loaded natively."""
    import subprocess as sp
    from paddle_tpu import native

    assert set(native.native_status()) == {"recordio", "graph", "lodpack"}
    calls = []
    real_run = sp.run

    def spy(cmd, **kw):
        calls.append(cmd)
        return real_run(cmd, **kw)

    monkeypatch.setattr(native.subprocess, "run", spy)
    monkeypatch.setattr(native, "_LIBS", {})
    loaded = native.load_library("lodpack")
    assert calls == [["make", "-C", os.path.dirname(native.__file__),
                      "liblodpack.so"]]
    assert native.load_library("lodpack") is loaded and len(calls) == 1

    def broken(cmd, **kw):
        raise sp.CalledProcessError(2, cmd)

    monkeypatch.setattr(native.subprocess, "run", broken)
    monkeypatch.setattr(native, "_LIBS", {})
    assert native.load_library("lodpack") is None
    assert native.native_status()["lodpack"] is False
