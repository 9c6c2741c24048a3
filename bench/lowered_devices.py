"""The devices of every program JAX compiles in this process.

JAX logs two DEBUG records for each program it obtains, a
persistent-cache hit included: ``Compiling <module> with global shapes
and types <avals>. Argument mapping: <shardings>`` when it lowers it, and
right after it ``get_compile_options: num_replicas=.. num_partitions=..
device_assignment=..`` when it builds the compile options.  The argument
mapping of a program whose input is an uncommitted array (the full-mode
chunk executor's ``jnp.arange``) reads ``UnspecifiedValue`` and names no
device, so the device count is taken from the device assignment that
follows the module's own lowering record.

``watch()`` installs one ``LoweredDevices`` for the process; it takes the
records as they are made and lets no DEBUG record of those loggers go
further, so nothing more is printed.

It reads the same lowering records as ``bench/lowerings.py`` and must not
be installed in the same process as that module's ``watch()``: logging
stops at the first filter that rejects a record, so one of the two would
miss records, and each reads the logger's level after the other has set
it to DEBUG, so DEBUG records would be printed."""
from __future__ import annotations

import logging
from typing import Dict, List, Optional, Tuple

LOWERING_LOGGER = "jax._src.interpreters.pxla"
COMPILER_LOGGER = "jax._src.compiler"
_LOWERING = "Compiling %s with global shapes and types"
_OPTIONS = "get_compile_options:"


class LoweredDevices(logging.Filter):
    def __init__(self) -> None:
        super().__init__()
        self.seen: List[Tuple[str, int]] = []      # (module, devices)
        self._pending: Optional[str] = None
        self._shown: Dict[str, int] = {}
        for name in (LOWERING_LOGGER, COMPILER_LOGGER):
            logger = logging.getLogger(name)
            self._shown[name] = logger.getEffectiveLevel()
            logger.addFilter(self)
            logger.setLevel(logging.DEBUG)

    def filter(self, record: logging.LogRecord) -> bool:
        if isinstance(record.msg, str):
            if record.msg.startswith(_LOWERING):
                self._pending = str(record.args[0])
            elif record.msg.startswith(_OPTIONS) and self._pending:
                assignment = record.args[2]
                self.seen.append((self._pending,
                                  int(getattr(assignment, "size", 1))))
                self._pending = None
        return record.levelno >= self._shown.get(record.name,
                                                 logging.WARNING)

    def device_counts(self, module: str) -> List[int]:
        """Devices of each compilation of ``module``."""
        return [n for name, n in self.seen if name == module]


_WATCH: Optional[LoweredDevices] = None


def watch() -> LoweredDevices:
    global _WATCH
    if _WATCH is None:
        _WATCH = LoweredDevices()
    return _WATCH
