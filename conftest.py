"""Root conftest: the same CPU-backend forcing tests/conftest.py does,
applied repo-wide so ``pytest --doctest-modules pydcop_tpu`` (the
doctest gate, reference Makefile:6) runs the package's docstring
examples under the 8-virtual-device CPU platform.  Tests never run on
an accelerator: ``chip_smoke.py`` is what runs on the chip."""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
