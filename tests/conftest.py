"""Test harness configuration.

Multi-chip paths are tested on a virtual 8-device CPU mesh (the analog of the
reference's oversubscribed ``mpiexec -np 8`` CI runs, SURVEY §4).  On a host
with a chip JAX would default to it, so the platform is set before the import
and asserted after — tests must be deterministic and must not occupy the chip.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

# the autotuner consult (parsec_tpu/tune) must be hermetic under test: a
# leftover /tmp/tunedb.jsonl from a bench run on the same box must never
# steer test Contexts.  env-level default, so tests that probe the
# consult path still override it with params.set / their own stores.
if "PARSEC_MCA_tune_db_path" not in os.environ:
    import tempfile

    os.environ["PARSEC_MCA_tune_db_path"] = os.path.join(
        tempfile.mkdtemp(prefix="parsec_test_tune_"), "tunedb.jsonl")

import jax  # noqa: E402

assert jax.default_backend() == "cpu", jax.default_backend()
assert len(jax.devices()) == 8, jax.devices()

import pytest  # noqa: E402


@pytest.fixture
def device_registry():
    """The device registry; whatever the test registers is dropped again
    afterwards."""
    from parsec_tpu.device import registry

    snapshot = list(registry.devices)
    yield registry
    registry.devices = snapshot
    for i, d in enumerate(registry.devices):
        d.device_index = i


@pytest.fixture
def accel_device(device_registry):
    """A TPUDevice wrapping the host CPU jax device, registered for the
    test and restored after (shared by the device/pressure suites)."""
    from parsec_tpu.device.tpu import TPUDevice

    return device_registry.add(TPUDevice(jax.devices()[0]))


@pytest.fixture
def param():
    """Scoped MCA-parameter override: set through the registry, restored
    at test exit (shared by every test module)."""
    from parsec_tpu.core.params import params
    saved = {}

    def set_(name, value):
        if name not in saved:       # keep the ORIGINAL for restore when a
            saved[name] = params.get(name)   # test overrides twice
        params.set(name, value)

    yield set_
    for name, value in saved.items():
        params.set(name, value)


_compile_requests = {"n": None}      # None until the listener is registered


@pytest.fixture
def compile_requests():
    """A callable giving the XLA compile requests this process has made so
    far (a persistent-cache hit is a request too), counted on
    ``jax.monitoring`` as the benchmark's ``CompileMeter`` counts them: a
    warm path is one that makes none."""
    if _compile_requests["n"] is None:
        import jax.monitoring as mon

        def on_duration(event, _secs, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                _compile_requests["n"] += 1

        _compile_requests["n"] = 0
        mon.register_event_duration_secs_listener(on_duration)
    return lambda: _compile_requests["n"]
