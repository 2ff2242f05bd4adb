"""Central jax configuration, imported before any jax use in the package.

Enables 64-bit types: SQL LONG/TIMESTAMP semantics require real int64.
On TPU int64 lowers to XLA's 32-bit-pair emulation (correct, slower);
float64 is narrowed to float32 at upload time instead (see
columnar/batch.py:physical_np_dtype) because TPUs have no f64 hardware.

Also the ONE place that decides where jax's persistent compilation cache
lives (`place_compile_cache`): every entry point reaches it through
TpuDeviceManager's bring-up, the first code to know the platform.
"""

import os
from typing import Optional

import jax

jax.config.update("jax_enable_x64", True)

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# fixed path: the directory is part of every entry's key, so a cache that
# moves (platform suffix, pid, temp name) never hits
DEFAULT_COMPILE_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")

# where this process keeps compiled programs (None = nowhere); set by
# place_compile_cache, read by chip_smoke.py and bench.py for reporting
compile_cache_dir: Optional[str] = None


def place_compile_cache(platform: str) -> Optional[str]:
    """Decide the persistent compile cache's directory, once the platform
    is known and before the engine's first compile.

    - JAX_COMPILATION_CACHE_DIR set: jax reads it itself; nothing is set in
      code, so a cache placed from outside stays where it was put and keeps
      the policy it was given (size cap, JAX_PERSISTENT_CACHE_MIN_*).
    - unset on the cpu backend: no cache (the test suite writes none).
    - unset on an accelerator: <checkout>/.jax_cache, and every program is
      kept there, however quickly it compiled: the directory has no size
      cap, and an engine of many small kernels otherwise recompiles
      hundreds of them on each start.
    """
    global compile_cache_dir
    compile_cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or None
    if compile_cache_dir is None and platform != "cpu":
        os.makedirs(DEFAULT_COMPILE_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir",
                          DEFAULT_COMPILE_CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        compile_cache_dir = DEFAULT_COMPILE_CACHE_DIR
    return compile_cache_dir
