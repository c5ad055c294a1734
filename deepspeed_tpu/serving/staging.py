"""A serve program's host-made inputs as ONE packed ``int32`` array.

A serve step hands each of its two programs a handful of small values
the host made: tokens, positions, sampling parameters, page tables.
Staged leaf by leaf, every one is its own call into PJRT (~0.3 ms each
on the chip); packed, a program costs one ``jax.device_put``.

One layout serves every program of every cache kind: its only parameter
is the program's **field list**, ``(name, shape, dtype)`` in the order
the program reads them (serving/engine.py declares the two lists).  The
fields lie end to end in one ``int32`` vector, each at a static offset,
and travel **bit-exact, not converted**: a ``float32`` temperature and a
``uint32`` seed as their bits (``ndarray.view`` on the host,
``jax.lax.bitcast_convert_type`` in the program), a boolean as 0 / 1
(``!= 0`` in the program).  Nothing is rounded, so a program unpacks
exactly the values the separate leaves carried.
"""
from __future__ import annotations

from typing import Dict, Iterable, NamedTuple, Tuple

import numpy as np

_WORD = np.dtype(np.int32)


class Field(NamedTuple):
    name: str
    shape: Tuple[int, ...]
    dtype: np.dtype
    offset: int  # in words, into the packed vector
    size: int


class PackedLayout:
    """Static offsets of a field list into one ``int32`` vector."""

    def __init__(self, fields: Iterable[Tuple[str, Tuple[int, ...], object]]):
        laid, offset = [], 0
        for name, shape, dtype in fields:
            dtype = np.dtype(dtype)
            if dtype != np.bool_ and dtype.itemsize != _WORD.itemsize:
                raise ValueError(f"field {name!r}: {dtype} is neither a boolean nor one 32-bit word an element")
            size = int(np.prod(shape, dtype=np.int64))
            laid.append(Field(name, tuple(int(d) for d in shape), dtype, offset, size))
            offset += size
        self.fields: Tuple[Field, ...] = tuple(laid)
        self.size = offset
        if len({f.name for f in self.fields}) != len(self.fields):
            raise ValueError(f"duplicate field names: {[f.name for f in self.fields]}")

    # -- host side ---------------------------------------------------------
    def buffer(self) -> np.ndarray:
        return np.zeros((self.size,), _WORD)

    def views(self, packed: np.ndarray) -> Dict[str, np.ndarray]:
        """Each field as a shaped view of the host vector ``packed``, in
        the field's own dtype — a boolean's as ``int32`` holding 0 / 1 —
        so that a write through the view lays the value's bits."""
        if packed.dtype != _WORD or packed.shape != (self.size,):
            raise ValueError(f"views: an int32 vector of {self.size} words, got {packed.dtype}{packed.shape}")
        return {
            f.name: packed[f.offset:f.offset + f.size]
            .view(_WORD if f.dtype == np.bool_ else f.dtype).reshape(f.shape)
            for f in self.fields
        }

    def pack(self, **values) -> np.ndarray:
        """A vector made afresh from one value a field: what filling a
        kept buffer through :meth:`views` must equal (the tests hold the
        engine to it)."""
        if values.keys() != {f.name for f in self.fields}:
            raise ValueError(f"pack: fields {[f.name for f in self.fields]}, got {sorted(values)}")
        out = np.empty((self.size,), _WORD)
        for name, view in self.views(out).items():
            view[...] = values[name]
        return out

    # -- in the program ----------------------------------------------------
    def unpack(self, packed) -> Dict[str, object]:
        """Static slices of the staged vector, each back in its field's
        shape and dtype (traceable: the head of a jitted program)."""
        import jax
        import jax.numpy as jnp

        out = {}
        for f in self.fields:
            words = packed[f.offset:f.offset + f.size].reshape(f.shape)
            if f.dtype == np.bool_:
                out[f.name] = words != 0
            elif f.dtype == _WORD:
                out[f.name] = words
            else:
                out[f.name] = jax.lax.bitcast_convert_type(words, jnp.dtype(f.dtype))
        return out


__all__ = ["Field", "PackedLayout"]
