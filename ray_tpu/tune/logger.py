"""Result loggers.

Parity: `python/ray/tune/logger.py` — `JsonLogger` (:100), `CSVLogger`
(:277), `TBXLogger` (:315), `UnifiedLogger` (:383).
"""

from __future__ import annotations

import csv
import json
import logging
import os
import socket
import struct
import time
from typing import List, Optional

import numpy as np

logger = logging.getLogger(__name__)


class Logger:
    def __init__(self, config: dict, logdir: str):
        self.config = config
        self.logdir = logdir
        self._init()

    def _init(self):
        pass

    def on_result(self, result: dict):
        raise NotImplementedError

    def update_config(self, config: dict):
        self.config = config

    def flush(self):
        pass

    def close(self):
        pass


class _SafeJson(json.JSONEncoder):
    def default(self, o):
        if isinstance(o, np.integer):
            return int(o)
        if isinstance(o, np.floating):
            return float(o)
        if isinstance(o, np.ndarray):
            return o.tolist()
        try:
            return super().default(o)
        except TypeError:
            return str(o)


class JsonLogger(Logger):
    def _init(self):
        config_path = os.path.join(self.logdir, "params.json")
        with open(config_path, "w") as f:
            json.dump(self.config, f, cls=_SafeJson, indent=2)
        self._file = open(os.path.join(self.logdir, "result.json"), "a")

    def on_result(self, result: dict):
        json.dump(result, self._file, cls=_SafeJson)
        self._file.write("\n")
        self._file.flush()

    def update_config(self, config):
        super().update_config(config)
        with open(os.path.join(self.logdir, "params.json"), "w") as f:
            json.dump(config, f, cls=_SafeJson, indent=2)

    def close(self):
        self._file.close()


def _flatten(d: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in d.items():
        key = f"{prefix}/{k}" if prefix else str(k)
        if isinstance(v, dict):
            out.update(_flatten(v, key))
        else:
            out[key] = v
    return out


class CSVLogger(Logger):
    def _init(self):
        self._file = open(os.path.join(self.logdir, "progress.csv"), "a")
        self._writer = None

    def on_result(self, result: dict):
        flat = _flatten({k: v for k, v in result.items()
                         if not isinstance(v, (list, np.ndarray))})
        scalar = {k: v for k, v in flat.items()
                  if isinstance(v, (int, float, str, bool, np.number))}
        if self._writer is None:
            self._writer = csv.DictWriter(self._file,
                                          fieldnames=sorted(scalar))
            self._writer.writeheader()
        self._writer.writerow(
            {k: scalar.get(k, "") for k in self._writer.fieldnames})
        self._file.flush()

    def close(self):
        self._file.close()


def _crc32c_table():
    table = []
    for n in range(256):
        for _ in range(8):
            n = (n >> 1) ^ (0x82F63B78 if n & 1 else 0)
        table.append(n)
    return table


_CRC32C = _crc32c_table()


def _masked_crc32c(data: bytes) -> bytes:
    crc = 0xFFFFFFFF
    for b in data:
        crc = _CRC32C[(crc ^ b) & 0xFF] ^ (crc >> 8)
    crc ^= 0xFFFFFFFF
    masked = (((crc >> 15) | (crc << 17)) + 0xA282EAD8) & 0xFFFFFFFF
    return struct.pack("<I", masked)


def _varint(n: int) -> bytes:
    out = bytearray()
    while n > 0x7F:
        out.append((n & 0x7F) | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def _len_field(number: int, payload: bytes) -> bytes:
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def _event(wall_time: float, step: int = 0, file_version: str = "",
           scalars=()) -> bytes:
    """One serialized `tensorboard.Event` (event.proto: wall_time=1
    double, step=2 int64, file_version=3, summary=5; summary.proto:
    Summary.value=1, Value.tag=1, Value.simple_value=2 float)."""
    out = b"\x09" + struct.pack("<d", wall_time)
    if step:
        out += b"\x10" + _varint(step)
    if file_version:
        out += _len_field(3, file_version.encode())
    if scalars:
        out += _len_field(5, b"".join(
            _len_field(1, _len_field(1, tag.encode())
                       + b"\x15" + struct.pack("<f", value))
            for tag, value in scalars))
    return out


class TBXLogger(Logger):
    """TensorBoard scalars: an `events.out.tfevents.*` file of TFRecord
    frames written here byte by byte, so a trial's process imports no
    framework for it (torch's SummaryWriter cost every trial ~10 s)."""

    def _init(self):
        now = time.time()
        self._file = open(os.path.join(
            self.logdir, "events.out.tfevents.%010d.%s.%d" % (
                now, socket.gethostname(), os.getpid())), "ab")
        self._write(_event(now, file_version="brain.Event:2"))

    def _write(self, event: bytes):
        header = struct.pack("<Q", len(event))
        self._file.write(header + _masked_crc32c(header)
                         + event + _masked_crc32c(event))
        self._file.flush()

    def on_result(self, result: dict):
        scalars = [(k, float(v)) for k, v in _flatten(result).items()
                   if isinstance(v, (int, float, np.number))
                   and np.isfinite(v)]
        self._write(_event(time.time(),
                           int(result.get("training_iteration", 0)),
                           scalars=scalars))

    def close(self):
        self._file.close()


DEFAULT_LOGGERS = (JsonLogger, CSVLogger, TBXLogger)


class UnifiedLogger(Logger):
    def __init__(self, config: dict, logdir: str,
                 loggers: Optional[List] = None):
        self._logger_classes = loggers or list(DEFAULT_LOGGERS)
        super().__init__(config, logdir)

    def _init(self):
        self._loggers = []
        for cls in self._logger_classes:
            try:
                self._loggers.append(cls(self.config, self.logdir))
            except Exception:
                logger.exception("could not start logger %s", cls)

    def on_result(self, result: dict):
        for lg in self._loggers:
            lg.on_result(result)

    def update_config(self, config):
        for lg in self._loggers:
            lg.update_config(config)

    def flush(self):
        for lg in self._loggers:
            lg.flush()

    def close(self):
        for lg in self._loggers:
            lg.close()
