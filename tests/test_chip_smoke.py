"""Rehearsals of ``chip_smoke.py`` on the CPU at tiny sizes.

The phase functions are called directly (the script's ``main`` refuses the
CPU): phase A on small paper-style matrices, phase B on the reduced
sparse-FFN preset, and the four-chip sharded phase on 4 forced host devices
in a child process.
"""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
TINY = ((10, 10, 20, True), (5, 5, 10, False))


@pytest.fixture
def smoke(tmp_path):
    sys.path.insert(0, ROOT)
    try:
        import chip_smoke
        from repro.core.autotune import reset_autotune_stats
        from repro.core.cache import set_default_cache
        from repro.core.staging import clear_cache

        reset_autotune_stats()
        chip_smoke.fresh_plan_cache(str(tmp_path / "plans"))
        yield chip_smoke
    finally:
        set_default_cache(None)
        clear_cache()
        sys.path.remove(ROOT)


def test_main_refuses_the_cpu(smoke, capsys):
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_phase_kernels_tiny(smoke, capsys):
    smoke.phase_kernels(smoke.paper_matrices(200, TINY), n_cols=8, on_tpu=False)
    out = capsys.readouterr().out
    assert "spmv autotune" in out and "spmm autotuned" in out


def test_phase_server_tiny(smoke, capsys):
    from repro.configs import llama3_8b

    smoke.phase_server(llama3_8b.reduced_sable(), prompt_len=8, new_tokens=4,
                       on_tpu=False)
    assert "tokens agreeing with engine.generate" in capsys.readouterr().out


def test_phase_sharded_on_4_host_devices(tmp_path):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    script = f"""
        import sys
        sys.path.insert(0, {ROOT!r})
        import chip_smoke
        chip_smoke.fresh_plan_cache({str(tmp_path / "plans")!r})
        chip_smoke.phase_sharded(chip_smoke.paper_matrices(200, {TINY!r}),
                                 n_cols=8)
        print("OK")
    """
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(script)],
        capture_output=True, text=True, env=env, timeout=560,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    assert "OK" in out.stdout and "2-D (2, 2)" in out.stdout
