from tpustack.utils import knobs
from tpustack.utils.config import (device_info, enable_compile_cache,
                                   require_accelerator)
from tpustack.utils.logging import get_logger

__all__ = ["device_info", "enable_compile_cache", "get_logger", "knobs",
           "require_accelerator"]
