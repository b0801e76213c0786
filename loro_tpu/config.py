"""Runtime configuration (reference: crates/loro-internal/src/configure.rs)."""
from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Dict


@dataclass
class Configure:
    record_timestamp: bool = False
    merge_interval_s: int = 1000  # change RLE-merge window (reference default 1000s)
    editable_detached_mode: bool = False
    hide_empty_root_containers: bool = False
    # style expand behavior per key: "after" (default), "before", "both", "none"
    text_style_config: Dict[str, str] = field(default_factory=dict)
    # expand behavior for keys absent from text_style_config
    # (reference: LoroDoc::config_default_text_style)
    default_text_style: str = "after"
    # tree sibling positions: fractional indexes on create/move
    # (reference: Tree::enable/disable_fractional_index)
    fractional_index_enabled: bool = True
    fractional_index_jitter: int = 0


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at one fixed place and
    return it.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
    uses it and nothing is changed; otherwise the cache goes to
    ``<checkout>/.jax_cache`` — never a temp name, pid or time, because
    the path is part of the cache key.  Entry points (chip_smoke.py,
    benchmarks/run.py, the examples) call this before their first compile; no
    other code of the tree sets the cache directory."""
    import jax  # lazily: host-engine users of the package never load it

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache",
        )
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
