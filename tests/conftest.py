import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# Tests see exactly ONE device by default (the dry-run sets its own
# 512-device flag in a subprocess). The sharded smoke lane opts into fake
# host devices via REPRO_DRYRUN_DEVICES=N (must happen before the first
# jax backend init); tests needing multiple devices skip when absent.
from repro.host_devices import force_host_device_count  # noqa: E402

force_host_device_count(argv=())

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_x64", False)


@pytest.fixture(scope="session")
def reduced():
    """Session-cached (cfg, bundle, params) per architecture.

    Building the reduced config + abstract specs + init_params for the same
    arch in several tests re-traces the same init graph each time; the suite
    uses this factory instead. Params are jax arrays (immutable) — tests
    must not mutate the returned dict in place.
    """
    from repro.configs import get_reduced_config
    from repro.models import build
    from repro.models.params import init_params

    cache = {}

    def get(arch: str):
        if arch not in cache:
            cfg = get_reduced_config(arch)
            bundle = build(cfg)
            params = init_params(bundle.param_specs, jax.random.PRNGKey(0))
            cache[arch] = (cfg, bundle, params)
        return cache[arch]

    return get
