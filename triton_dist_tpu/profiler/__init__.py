from triton_dist_tpu.profiler.language import (  # noqa: F401
    Profiler, record,
)
from triton_dist_tpu.profiler.viewer import (  # noqa: F401
    export_to_perfetto_trace,
)
from triton_dist_tpu.profiler_utils import group_profile, perf_func  # noqa: F401
