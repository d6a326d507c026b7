"""Package ``__init__`` names that resolve on first use (PEP 562).

A process that holds no chip (the agent, the gateway, the local master)
must never import JAX, yet shares packages with code that needs it; such a
package lists where each public name lives instead of importing it."""

import importlib
from typing import Callable, Dict


def lazy_exports(package: str, table: Dict[str, str]) -> Callable:
    """The module-level ``__getattr__`` for ``package``: ``table`` maps a
    public name to the module that defines it."""

    def __getattr__(name):
        if name not in table:
            raise AttributeError(
                f"module {package!r} has no attribute {name!r}"
            )
        return getattr(importlib.import_module(table[name]), name)

    return __getattr__
