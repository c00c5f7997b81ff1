"""JAX's persistent compilation cache, placed for every entry point.

A TPU boot compiles every bucket and every fused decode program before it
takes traffic; a second boot of the same checkout should read them back.
The cache directory is part of the cache key, so it must never move
between runs: it is wherever ``JAX_COMPILATION_CACHE_DIR`` says, and
otherwise ONE fixed directory inside the checkout — no temp name, pid or
timestamp. Called by the process mains (serving/server.py, platform.py,
serving/microservice.py, tools/soak.py, benchmarks/run.py, chip_smoke.py);
library code and tests never touch it.
"""

from __future__ import annotations

import os
import re

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# <checkout>/.jax_cache (git-ignored): the parent of the package directory
DEFAULT_CACHE_DIR = os.path.join(CHECKOUT_ROOT, ".jax_cache")


def enable_compile_cache() -> str | None:
    """Turn the persistent cache on before the first compile; returns the
    directory in use (None = not caching). With
    ``JAX_COMPILATION_CACHE_DIR`` set the directory is JAX's own to read —
    nothing is set in code. A process pinned to the CPU backend
    (``JAX_PLATFORMS=cpu``: tests, the CPU smokes) gets no default
    directory: CPU compiles are cheap and XLA logs two lines per
    executable it reads back."""
    import jax

    # the decode tier is dozens of sub-second programs: JAX's default
    # 1 s minimum compile time would admit almost none of them (the
    # minimum entry size already defaults to 0 = admit everything)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # the key covers the ops' metadata: by default it does not, and a cache
    # warmed by a build whose programs differ only in their named scopes
    # (models/decoder.py PAGED_SCOPES) hands back executables without them —
    # a device trace then names nothing. Source paths enter the key relative
    # to the checkout, so a checkout at another path still hits.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update(
        "jax_hlo_source_file_canonicalization_regex", re.escape(CHECKOUT_ROOT + os.sep)
    )
    if os.environ.get(CACHE_DIR_ENV):
        return os.environ[CACHE_DIR_ENV]
    if jax.config.jax_platforms == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
