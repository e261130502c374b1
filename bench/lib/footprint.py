"""What a provider's dependency pool holds, measured by the harness itself.

:func:`reachable_bytes` walks every object reachable from the pool's roots and
adds up the array buffers it finds, each buffer once: host numpy arrays (a view
counts its base), device arrays, and raw byte strings. The walk does not ask
the program what it holds, so a tier the program adds later (device pages, a
second copy per function) is counted without a change here. Functions,
compiled executables and modules are not followed: they hold code, and through
their globals, everything else.
"""
from __future__ import annotations

import collections
import os
import types
from typing import Any, Iterable

import numpy as np

_SKIP = (str, int, float, complex, bool, type(None), type, types.ModuleType,
         types.FunctionType, types.BuiltinFunctionType, types.MethodType)


def reachable_bytes(*roots: Any) -> int:
    """Bytes of the distinct array buffers reachable from ``roots``."""
    import jax
    jax_array = jax.Array
    seen_objs, seen_bufs = set(), set()
    total = 0
    stack = list(roots)
    while stack:
        obj = stack.pop()
        if id(obj) in seen_objs:
            continue
        seen_objs.add(id(obj))
        if isinstance(obj, np.ndarray):
            base = obj
            while isinstance(base.base, np.ndarray):
                base = base.base
            owner = base.base if base.base is not None else base
            if id(owner) not in seen_bufs:
                seen_bufs.add(id(owner))
                total += (len(owner) if isinstance(owner, (bytes, bytearray))
                          else base.nbytes)
            continue
        if isinstance(obj, jax_array):
            if id(obj) not in seen_bufs:
                seen_bufs.add(id(obj))
                total += obj.nbytes
            continue
        if isinstance(obj, (bytes, bytearray)):
            if id(obj) not in seen_bufs:
                seen_bufs.add(id(obj))
                total += len(obj)
            continue
        if isinstance(obj, _SKIP) or callable(obj):
            continue
        if isinstance(obj, dict):
            stack.extend(obj.keys())
            stack.extend(obj.values())
        elif isinstance(obj, (list, tuple, set, frozenset, collections.deque)):
            stack.extend(obj)
        else:
            d = getattr(obj, "__dict__", None)
            if d is not None:
                stack.extend(d.values())
            for slot in getattr(type(obj), "__slots__", ()):
                if isinstance(slot, str) and hasattr(obj, slot):
                    stack.append(getattr(obj, slot))
    return total


def directory_bytes(path: str) -> int:
    """Bytes of the files under ``path`` (a pool's disk tier); 0 if absent."""
    total = 0
    for dirpath, _, files in os.walk(path):
        for f in files:
            total += os.path.getsize(os.path.join(dirpath, f))
    return total


def pool_bytes(roots: Iterable[Any], disk_dir: str) -> int:
    """All tiers of a pool: arrays reachable from ``roots`` plus its disk tier."""
    return reachable_bytes(*roots) + directory_bytes(disk_dir)
