import os
import sys

# src-layout import path (mirrors PYTHONPATH=src), plus the repo root for
# the benchmark configs (mirrors running from the root)
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(1, os.path.join(os.path.dirname(__file__), ".."))

import jax  # noqa: E402

# keep smoke tests on the single real device; dryrun.py sets its own flags
jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    # tier-1 lane guard: calling a legacy runner shim (run_simulation /
    # run_ensemble / run_sweep / run_scenarios) from in-repo code fails
    # the suite — only pytest.warns(APIDeprecationWarning)-shielded shim
    # tests may touch them. Registered here (not pytest.ini) because the
    # ini filters are parsed before this conftest puts src/ on sys.path.
    config.addinivalue_line(
        "filterwarnings",
        "error::repro.utils.deprecation.APIDeprecationWarning",
    )
