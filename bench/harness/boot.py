"""Start-up shared by the benchmark and its tools: the program on the
path, JAX's persistent compilation cache inside the checkout (every
program cached, so only a checkout's first run compiles), and the chip
check."""
from __future__ import annotations

import sys

from . import device
from .registry import ROOT


def program_present() -> bool:
    return (ROOT / "src" / "repro").is_dir()


def start(chips: int) -> dict:
    """Returns the device description; raises ``device.NoChip``."""
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    from repro.launch import compile_cache

    compile_cache.place(ROOT)
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return device.check(chips)
