"""chip_smoke.py on the CPU (ISSUE 21): the smoke's body is a function of
(docs, shards, expected platform) — here 2,000 documents on `cpu` — and
its entry point refuses anything but a TPU. Also pins where the package
puts the XLA compile cache."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_args, env_extra):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run([sys.executable, *code_or_args], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=300)


def test_smoke_body_on_cpu():
    import chip_smoke
    # conftest's eight virtual devices: five shards pad to an 8-wide mesh,
    # so the smoke holds steps (d) and (e) to the mesh lane
    out = chip_smoke.run(2_000, 5, "cpu", n_devices=8)
    assert out["platform"] == "cpu" and out["docs"] == 2_000
    assert out["shards"] == 5 and out["reduced"] == []
    for step in ("a1", "a2", "b", "c"):
        assert out["lanes"][step] == ["packed"], out["lanes"]
    assert out["lanes"]["d"] == out["lanes"]["e"] == ["mesh"], out["lanes"]
    assert not os.path.exists(chip_smoke.DATA_DIR)


def test_entry_point_refuses_the_cpu():
    r = _run(["chip_smoke.py"], {})
    assert r.returncode != 0
    assert "needs [tpu]" in r.stderr
    assert "indexed" not in r.stdout and '"ok"' not in r.stdout


_PRINT_CACHE = ("import jax, elasticsearch_tpu; "
                "print(jax.config.jax_compilation_cache_dir)")


def test_compile_cache_dir_env_is_left_alone(tmp_path):
    r = _run(["-c", _PRINT_CACHE],
             {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == str(tmp_path)


def test_compile_cache_dir_default_is_in_the_checkout():
    r = _run(["-c", _PRINT_CACHE], {})
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == os.path.join(REPO, ".xla_cache")
