from triton_dist_tpu.models.config import ModelConfig  # noqa: F401
from triton_dist_tpu.models.kv_cache import KVCache  # noqa: F401
from triton_dist_tpu.models import dense  # noqa: F401
from triton_dist_tpu.models import qwen_moe  # noqa: F401
from triton_dist_tpu.models import qwen_next  # noqa: F401
from triton_dist_tpu.models import latent_moe  # noqa: F401
from triton_dist_tpu.models import checkpoint  # noqa: F401
from triton_dist_tpu.models.engine import Engine  # noqa: F401
