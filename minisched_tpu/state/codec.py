"""Compiled JSON codec for the API object tree.

The wire layer converts objects ↔ JSON dicts constantly — the apiserver
serializes every stored object it lists/watches and the client rebuilds
every one of them (reference: client-go's codec does this for every REST
round-trip). Measured on the wire bench's 2000-pod burst, the generic
reflective paths were the single largest wire cost: ~75 µs/pod to decode
via per-field ``typing.get_origin``/``get_args`` walks and ~40 µs/pod to
encode via ``dataclasses.asdict`` (which deep-walks with its own
reflection). This module compiles, ONCE per dataclass, closure pipelines
with all reflection resolved at compile time — the hot path is plain
attribute reads and dict/list constructors.

Contract (identical to the reflective implementations it replaces):
  * ``dump(o)`` returns freshly-constructed containers at every level —
    callers may mutate the result (the client does, e.g. zeroing
    metadata.resource_version on unconditional PUTs).
  * ``build(cls, d)`` tolerates MISSING fields (dataclass defaults
    apply — old snapshots, hand-written test dicts) and ignores unknown
    keys; a dict carrying exactly the full field set takes a positional
    fast path with no intermediate kwargs dict.
"""
from __future__ import annotations

import dataclasses
import typing
from typing import Any, Callable, Dict, Optional

# ``None`` as a compiled codec means identity (scalars / Any): callers
# exploit it to collapse containers of scalars into plain list()/dict()
# copies instead of per-element calls.
_MaybeFn = Optional[Callable[[Any], Any]]

_BUILDERS: Dict[Any, _MaybeFn] = {}
_DUMPERS: Dict[Any, _MaybeFn] = {}


def _compile_builder(tp: Any) -> _MaybeFn:
    origin = typing.get_origin(tp)
    if origin is typing.Union:
        inner = [a for a in typing.get_args(tp) if a is not type(None)]
        sub = _builder(inner[0]) if len(inner) == 1 else None
        if sub is None:
            return None  # Optional[scalar] / unions: identity (None flows)
        return lambda v: None if v is None else sub(v)
    if dataclasses.is_dataclass(tp):
        hints = typing.get_type_hints(tp)
        fields = dataclasses.fields(tp)
        names = tuple(f.name for f in fields)
        keyset = frozenset(names)
        subs = tuple(_builder(hints[n]) for n in names)
        pairs = tuple(zip(names, subs))
        new = object.__new__  # no API dataclass defines __post_init__,
        # so the full-dict fast path may bypass __init__ entirely: no
        # default checks, no default_factory calls (notably: a wire uid
        # is PRESERVED without burning a local _next_uid value), just one
        # store per slot (the classes are slotted: no __dict__ to fill).

        def build(v, _tp=tp, _keys=keyset, _pairs=pairs, _new=new):
            if v is None:
                return None
            if v.keys() == _keys:
                o = _new(_tp)
                for n, s in _pairs:
                    setattr(o, n, s(v[n]) if s is not None else v[n])
                return o
            return _tp(**{n: (s(v[n]) if s is not None else v[n])
                          for n, s in _pairs if n in v})
        return build
    if origin in (list, set, tuple):
        args = typing.get_args(tp)
        elem = _builder(args[0]) if args else None
        ctor = list if origin is list else origin
        if elem is None:
            return lambda v: None if v is None else ctor(v)
        return lambda v: None if v is None else ctor(elem(x) for x in v)
    if origin is dict:
        args = typing.get_args(tp)
        velem = _builder(args[1]) if len(args) == 2 else None
        if velem is None:
            return lambda v: None if v is None else dict(v)
        return lambda v: (None if v is None
                          else {k: velem(x) for k, x in v.items()})
    return None  # scalar / Any: identity


def _builder(tp: Any) -> _MaybeFn:
    try:
        return _BUILDERS[tp]
    except (KeyError, TypeError):  # TypeError: unhashable typing artifact
        fn = _compile_builder(tp)
        try:
            _BUILDERS[tp] = fn
        except TypeError:
            pass
        return fn


def _compile_dumper(tp: Any) -> _MaybeFn:
    origin = typing.get_origin(tp)
    if origin is typing.Union:
        inner = [a for a in typing.get_args(tp) if a is not type(None)]
        sub = _dumper(inner[0]) if len(inner) == 1 else None
        if sub is None:
            return None
        return lambda v: None if v is None else sub(v)
    if dataclasses.is_dataclass(tp):
        hints = typing.get_type_hints(tp)
        pairs = tuple((f.name, _dumper(hints[f.name]))
                      for f in dataclasses.fields(tp))

        def dump(o, _pairs=pairs):
            if o is None:
                return None
            d = {}
            for n, s in _pairs:  # slotted classes: no __dict__ to index
                x = getattr(o, n)
                d[n] = s(x) if s is not None else x
            return d
        return dump
    if origin in (list, set, tuple):
        args = typing.get_args(tp)
        elem = _dumper(args[0]) if args else None
        if elem is None:
            return lambda v: None if v is None else list(v)
        return lambda v: None if v is None else [elem(x) for x in v]
    if origin is dict:
        args = typing.get_args(tp)
        velem = _dumper(args[1]) if len(args) == 2 else None
        if velem is None:
            return lambda v: None if v is None else dict(v)
        return lambda v: (None if v is None
                          else {k: velem(x) for k, x in v.items()})
    return None


def _dumper(tp: Any) -> _MaybeFn:
    try:
        return _DUMPERS[tp]
    except (KeyError, TypeError):
        fn = _compile_dumper(tp)
        try:
            _DUMPERS[tp] = fn
        except TypeError:
            pass
        return fn


def build(cls: type, d: Dict[str, Any]) -> Any:
    """JSON dict → instance of dataclass ``cls`` (compiled)."""
    fn = _builder(cls)
    if fn is None:
        raise TypeError(f"{cls!r} is not a compilable dataclass")
    return fn(d)


def dump(o: Any) -> Dict[str, Any]:
    """Dataclass instance → plain JSON-able dict (compiled); falls back
    to dataclasses.asdict for unregistered shapes."""
    fn = _dumper(type(o))
    if fn is None:
        return dataclasses.asdict(o)
    return fn(o)
