"""``chip_smoke.py`` rehearsed on the CPU at toy sizes.

The script itself refuses to run without a TPU; these tests call its
phases directly, so the control flow, the held-back-request reference
and every bitwise comparison are exercised before chip time is spent.
The four-chip phase needs four devices, which the CPU backend only
creates when the process starts, so it runs in a child process with
``--xla_force_host_platform_device_count=4``.
"""

import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402

TOY = dict(n_requests=16, n_fresh=64, n_actions=600, n_orders=300,
           n_users=40, horizon_ms=200_000)


def test_chip_smoke_refuses_cpu(capsys):
    assert chip_smoke.main([]) == 1
    out, err = capsys.readouterr()
    assert "no TPU found" in err
    assert '"ok"' not in out


def test_make_case_holds_back_last_action_per_key():
    case = chip_smoke.make_case(0, **TOY)
    users = case.tables["actions"].columns["userid"]
    req_users = users[case.req_idx]
    assert len(set(req_users.tolist())) == TOY["n_requests"]
    for u, i in zip(req_users, case.req_idx):
        assert i == np.flatnonzero(users == u)[-1]
    n_hist = len(case.history["actions"]) + TOY["n_requests"]
    assert n_hist == TOY["n_actions"]
    assert len(case.fresh) == TOY["n_fresh"]


def test_one_chip_phase_on_cpu(capsys):
    case = chip_smoke.make_case(0, **TOY)
    chip_smoke.one_chip(case, capacity=1024, batch=8)
    out = capsys.readouterr().out
    assert "staged serving vs offline(): 6 features bitwise equal" in out
    assert "fused serving vs staged serving: 6 features bitwise equal" \
        in out
    assert "fused offline() vs staged offline(): 6 features bitwise " \
        "equal" in out


def test_check_equal_names_the_differing_feature():
    a = {"x": np.zeros(3, np.float32), "y": np.ones(3, np.float32)}
    b = {"x": np.zeros(3, np.float32), "y": np.asarray([1, 2, 1],
                                                      np.float32)}
    with pytest.raises(chip_smoke.SmokeFailure, match="'y'"):
        chip_smoke.check_equal("t", a, b)


def test_kernel_parity_phase_on_cpu():
    """The unit-fold kernel (interpret mode here) against the jitted
    staged fold on every leaf family.  XLA:CPU's LLVM optimisation
    changes the last bit of some jitted ``avg_cate_where`` sums against
    the same program run eagerly (the kernel agrees with the eager
    run), so the child compiles without it; on a TPU the phase runs as
    ``chip_smoke.py`` does."""
    code = ("import chip_smoke as c\n"
            "c.kernel_parity(((37, 3, False), (200, 9, False), "
            "(300, 8, True)))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_backend_optimization_level=0")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    for rows, units, queries in ((37, 3, 37), (200, 9, 200), (300, 8, 1)):
        assert (f"kernel parity rows={rows} units={units} "
                f"queries={queries}:") in proc.stdout, proc.stdout


def test_four_chip_phase_on_four_virtual_devices():
    code = ("import chip_smoke as c\n"
            f"case = c.make_case(0, **{TOY!r})\n"
            "c.four_chip(case, capacity=1024, n_chips=4, batch=8)\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = proc.stdout
    assert "sharded store spread over 4 cpu devices" in out
    for what in ("sharded serving vs one-device serving",
                 "offline_sharded vs offline",
                 "serving after kill + heal vs one-device serving"):
        assert f"{what}: 6 features bitwise equal" in out, out


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    import jax
    from repro.compile_cache import enable_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_env_var_is_left_to_jax(monkeypatch, tmp_path):
    import jax
    from repro.compile_cache import enable_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    was = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was
