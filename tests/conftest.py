"""Test harness configuration.

Tests run on the CPU backend (``JAX_PLATFORMS=cpu``, the default set here
when the caller has not chosen one) with a virtual 8-device CPU mesh
(``--xla_force_host_platform_device_count=8``) — the moral equivalent of
the reference's ``local[4]`` Spark master (``README.md:38``, SURVEY.md §4).
Both must be in the environment before jax creates its first client.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()
# CLI invocations inside tests must not flip on the persistent compile
# cache (writes outside tmp_path).
os.environ["SPARK_EXAMPLES_TPU_NO_CACHE"] = "1"

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def small_source():
    from spark_examples_tpu.sources.synthetic import SyntheticGenomicsSource

    return SyntheticGenomicsSource(num_samples=40, seed=7, variant_spacing=100)
