from repro.pf.filter import (  # noqa: F401
    ParticleFilter,
    StateSpaceModel,
    run_filter,
    run_filter_bank,
)
from repro.pf.models import ungm, ungm_family, ungm_theta  # noqa: F401
from repro.pf.metrics import rmse  # noqa: F401
