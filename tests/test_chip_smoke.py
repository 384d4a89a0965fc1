"""The chip smoke test's phases, rehearsed on the CPU at a tiny size.

``chip_smoke.py`` refuses any backend but a TPU, so its phase functions
are driven here directly (Pallas in interpret mode): a broken serving
path, answer check or shard placement fails tier-1, not a chip call.
"""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
N_SMALL = 3000


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


cs = _load_smoke()


@pytest.fixture(scope="module")
def watchlist():
    return cs.build_watchlist(N_SMALL, seed=1)


def test_watchlist_holds_every_template_under_the_fleet_tenants(watchlist):
    (reg, gallery, _, _), ref = watchlist
    assert len(gallery) == N_SMALL
    rows = gallery.tenant_rows()
    assert rows.pop(None) == 0                 # nothing in the shared pool
    assert sum(rows.values()) == N_SMALL
    for name, n in rows.items():
        names, emb = ref[name]
        assert len(names) == emb.shape[0] == n
        np.testing.assert_allclose(np.linalg.norm(emb, axis=1), 1.0,
                                   rtol=1e-5)


@pytest.mark.parametrize("dtype,mode", cs.PHASES)
def test_serve_phase_matches_host_reference(watchlist, dtype, mode):
    fleet, ref = watchlist
    if mode == "ann" and not fleet[1].ann_indexed:
        fleet[1].build_ann_index(seed=1)
    with cs.CompileCounter() as counter:
        out = cs.serve_phase(fleet, ref, dtype, mode, duration_s=0.5)
    assert counter.compiles >= 0 and counter.compile_s >= 0.0
    assert out["lost"] == 0 and out["frames_in"] == out["frames_out"] > 0
    assert out["answered"] == out["frames_out"]
    assert out["max_score_err"] <= cs.SCORE_TOL[dtype]
    if dtype == "fp32":             # full precision resolves every subject
        assert out["same_label"] == out["answered"]
    assert "match" not in vars(fleet[1])       # the recorder is gone


def test_check_answers_rejects_a_wrong_label():
    names = np.asarray(["a", "b", "c"], object)
    rows = np.eye(3, 4, dtype=np.float32)
    ref = {"t": (names, rows)}
    q = np.asarray([[1.0, 0.1, 0.0, 0.0]], np.float32)
    good = [("t", q, np.asarray(["a"], object), np.asarray([0.995]))]
    assert cs.check_answers(good, ref, 1e-3)["same_label"] == 1
    for label, score in (("b", 0.0995), ("a", 0.9), ("z", 0.995)):
        bad = [("t", q, np.asarray([label], object), np.asarray([score]))]
        with pytest.raises(cs.SmokeFailure):
            cs.check_answers(bad, ref, 1e-3)


def test_served_kernel_text_is_the_compiled_match(watchlist):
    fleet, ref = watchlist
    text = cs.served_kernel_text(fleet, ref)
    assert text.startswith("HloModule")


def test_main_refuses_a_host_without_a_tpu(capsys):
    with pytest.raises(SystemExit) as exc:
        cs.main([])
    assert "no TPU" in str(exc.value.code)
    assert capsys.readouterr().out == ""


def _run(code: str, **env):
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"), **env)
    r = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=full,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


def test_sharded_phase_on_four_virtual_devices():
    """One shard per device on a four-device host, then all on one: the
    answers agree frame by frame."""
    out = _run(
        "import importlib.util as u\n"
        "s = u.spec_from_file_location('cs', 'chip_smoke.py')\n"
        "cs = u.module_from_spec(s); s.loader.exec_module(cs)\n"
        "r = cs.sharded_phase(2000, seed=2, duration_s=0.3)\n"
        "print(len(r['devices']), r['spread']['answered'],"
        " r['one_chip']['answered'])\n",
        XLA_FLAGS="--xla_force_host_platform_device_count=4")
    n_dev, a, b = map(int, out.split()[-3:])
    assert n_dev == 4 and a == b > 0


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_location(tmp_path, env_dir):
    code = ("import jax, jax.numpy as jnp\n"
            "from repro.launch.serve import use_compile_cache\n"
            "use_compile_cache()\n"
            "print(jax.config.jax_compilation_cache_dir)\n")
    if env_dir:                     # JAX reads the variable; a compile lands
        code += "jax.jit(lambda x: x * 3 + 1)(jnp.ones(7)).block_until_ready()\n"
        out = _run(code, JAX_COMPILATION_CACHE_DIR=str(tmp_path))
        assert out.split()[-1] == str(tmp_path)
        assert any(tmp_path.iterdir())
    else:
        out = _run(code)
        assert out.split()[-1] == str(ROOT / ".jax_cache")
