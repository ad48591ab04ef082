"""Where JAX's persistent compilation cache lives.

A cold ResNet-50 step takes the chip's compiler about half a minute, and
every process compiles it again unless the cache is on.  The cache's path is
part of its key, so it has to sit somewhere that does not move: where
``JAX_COMPILATION_CACHE_DIR`` is set — by the user or by the machine — JAX's
own handling of that variable is all there is; where it is not, the cache
goes to one fixed directory inside the checkout (listed in ``.gitignore``).
Never a temporary name, a process id or the time.

Imports no JAX: the launcher calls this for its ranks' environment and must
stay off the device.
"""

from __future__ import annotations

import os
import sys
from typing import MutableMapping, Optional

_ENV = "JAX_COMPILATION_CACHE_DIR"
_IN_TREE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_compilation_cache")


def place_compile_cache(env: Optional[MutableMapping[str, str]] = None) -> str:
    """Make ``env`` (default: this process's environment) name the cache
    directory, and return it: the variable's own value where it is set, the
    fixed in-tree directory where it is not.

    JAX reads the variable when it is imported.  Called on this process's
    environment after that, with the variable unset, the in-tree directory is
    handed to ``jax.config`` as well, so a script may call this anywhere
    before its first compile."""
    target = os.environ if env is None else env
    was_set = bool(target.get(_ENV))
    if not was_set:
        target[_ENV] = _IN_TREE
    if env is None and not was_set and "jax" in sys.modules:
        sys.modules["jax"].config.update("jax_compilation_cache_dir", _IN_TREE)
    return target[_ENV]
