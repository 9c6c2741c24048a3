"""The argument shapes of every program JAX lowers in this process.

JAX logs ``Compiling <module> with global shapes and types <avals>`` at
DEBUG level each time it lowers a program, a persistent-cache hit
included, so every program a run executes passes through it once.
``watch()`` installs one ``Lowerings`` for the process (JAX lowers a
program once per process, whichever run asks first); it takes those
records as they are made (the module name and the abstract values
themselves, not the formatted text) and lets no DEBUG record of that
logger go further, so nothing more is printed."""
from __future__ import annotations

import logging
from typing import List, Optional, Tuple

LOGGER = "jax._src.interpreters.pxla"
_MESSAGE = "Compiling %s with global shapes and types"


class Lowerings(logging.Filter):
    def __init__(self) -> None:
        super().__init__()
        self.seen: List[Tuple[str, Tuple[Tuple[int, ...], ...]]] = []
        logger = logging.getLogger(LOGGER)
        self._shown = logger.getEffectiveLevel()
        logger.addFilter(self)
        logger.setLevel(logging.DEBUG)

    def filter(self, record: logging.LogRecord) -> bool:
        if isinstance(record.msg, str) and record.msg.startswith(_MESSAGE):
            module, avals = record.args[0], record.args[1]
            self.seen.append((str(module), tuple(
                tuple(getattr(a, "shape", ())) for a in avals)))
        return record.levelno >= self._shown

    def arg_sizes(self, module: str, arg: int = 0) -> List[int]:
        """Element count of argument ``arg`` in each lowering of
        ``module``."""
        out = []
        for name, shapes in self.seen:
            if name == module and len(shapes) > arg:
                size = 1
                for d in shapes[arg]:
                    size *= int(d)
                out.append(size)
        return out


_WATCH: Optional[Lowerings] = None


def watch() -> Lowerings:
    global _WATCH
    if _WATCH is None:
        _WATCH = Lowerings()
    return _WATCH
