"""Native embedding C API: compile a pure-C host against
liblgbm_tpu.so and run the reference-style C-API workout
(tests/native_capi_driver.c) in a subprocess with no Python on its
stack — the seam R/Java hosts use (reference: R-package/src/
lightgbm_R.cpp links lib_lightgbm the same way)."""
import os
import subprocess

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE = os.path.join(REPO, "lightgbm_tpu", "native")
LIB = os.path.join(NATIVE, "liblgbm_tpu.so")
DRIVER_SRC = os.path.join(REPO, "tests", "native_capi_driver.c")



@pytest.mark.slow
def test_c_host_end_to_end(native_lib, tmp_path):
    exe = str(tmp_path / "capi_driver")
    inc_dir = os.path.join(NATIVE, "include")
    build = subprocess.run(
        ["gcc", "-O1", DRIVER_SRC, "-I", inc_dir, "-o", exe,
         "-L", NATIVE, "-llgbm_tpu", "-lm",
         f"-Wl,-rpath,{NATIVE}"],
        capture_output=True, text=True)
    assert build.returncode == 0, build.stderr
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    # the embedded interpreter is a child process: it runs JAX on CPU
    # and must never ask for a chip its parent could be holding
    env["JAX_PLATFORMS"] = "cpu"
    run = subprocess.run([exe, REPO], capture_output=True, text=True,
                         env=env, timeout=600)
    assert run.returncode == 0, f"stdout={run.stdout}\nstderr={run.stderr}"
    assert "NATIVE_CAPI_OK" in run.stdout
