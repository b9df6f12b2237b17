from .config import Config, global_config  # noqa: F401
from .context import ZooTpuContext, get_context, init_tpu_context, reset_context  # noqa: F401
from . import triggers  # noqa: F401
from .utils import time_it, tree_num_params, tree_size_bytes  # noqa: F401
