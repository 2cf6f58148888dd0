"""Device time by the program's own named scopes.

The program names its work with ``jax.named_scope``: ``zebra.<site>``
around everything a Zebra site runs (``core/engine.py``), and one scope
per CNN layer group (``stem``, ``s<i>b<j>``, ``head`` in
``models/cnn/resnet.py``). Every compiled instruction, fused or not,
carries its scope path as the ``op_name`` of its metadata, e.g.
``jit(<lambda>)/s0b1/zebra.z3/reduce_max``.

A trace record (``trace.extract``) names each op by its instruction and
output type only. This module compiles the cell's program again for the
shapes its window ran, reads ``instruction -> op_name`` from its
compiled text, and sums the trace's device time by scope. A record counts only
when every op in its window is an instruction of that program with the
same output type; otherwise the readers here give nothing rather than a
share of the wrong program.

A fusion takes the ``op_name`` of its root, so work fused into another
scope's fusion counts there. Two readings bound a scope's time: the ops
whose own ``op_name`` lies under it (``scope_seconds``, a floor), and the
ops any of whose instructions, fused ones included, lie under it, each
counted whole (``touch_seconds``, a ceiling).
"""
from __future__ import annotations

import functools
import json
import re

from chipbench.metrics.lib import trace as tr

_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')


_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(.*\) -> .*\{$")
_CALLED = re.compile(r"(?:calls|to_apply)=(%[\w.\-]+)")


def _instructions(hlo_text: str):
    """``(computation, key, op_name, called computations)`` of each
    instruction of compiled HLO text, keyed as ``trace.op_name`` names a
    trace's ops (``""`` where an instruction has no ``op_name``)."""
    comp = None
    for line in hlo_text.splitlines():
        line = line.strip()
        m = _COMPUTATION.match(line)
        if m:
            comp = "%" + m.group(1)
            continue
        if line.startswith("ROOT "):
            line = line[5:]
        if comp is None or not line.startswith("%"):
            continue
        m = _OP_NAME.search(line)
        yield (comp, tr.op_name(line), m.group(1) if m else "",
               _CALLED.findall(line))


def op_scopes(hlo_text: str) -> dict[str, str]:
    """Compiled HLO text -> ``{"<instruction> <output type>": op_name}``."""
    return {key: name for _, key, name, _ in _instructions(hlo_text)}


def op_contents(hlo_text: str) -> dict[str, frozenset[str]]:
    """Compiled HLO text -> ``{"<instruction> <output type>": op_names}``:
    the instruction's own ``op_name`` and those of every instruction in
    the computations it calls (fusions, reductions), nested ones too."""
    own, calls, body = {}, {}, {}
    for comp, key, name, called in _instructions(hlo_text):
        own[key], calls[key] = name, called
        body.setdefault(comp, []).append(key)

    @functools.cache
    def inside(comp: str) -> frozenset[str]:
        return frozenset().union(*(contents(k) for k in body.get(comp, ())))

    @functools.cache
    def contents(key: str) -> frozenset[str]:
        return frozenset({own[key]}).union(*(inside(c) for c in calls[key]))

    return {k: contents(k) - {""} for k in own}


def under(op_name: str, scope: str) -> bool:
    """Whether ``op_name`` has a path component ``scope`` or
    ``scope.<anything>`` (``zebra`` matches ``zebra.z3``, not
    ``jit(zebra_mask_pack)``)."""
    return any(c.split(".")[0] == scope for c in op_name.split("/"))


def _window_ops(rec: dict, dev: str):
    """``(name, start, end)`` of the device's ops clipped to the window,
    loops and branches left out (their bodies' ops are in the record)."""
    for name, a, b in tr._clip(tr._events(rec, dev, "ops"), *rec["window"]):
        if tr.base_name(name).split(" ")[0] not in tr.CONTAINERS:
            yield name, a, b


def covers(rec: dict, scopes: dict[str, str], dev: str = "0") -> bool:
    """Whether every op the window ran is an instruction of the compiled
    program behind ``scopes``, with the same output type."""
    return all(name in scopes for name, _, _ in _window_ops(rec, dev))


def _seconds(rec: dict, dev: str, pick) -> float:
    """Device seconds in the window of the ops ``pick(name)`` keeps: the
    union of their intervals, so it never exceeds the busy time."""
    return sum(b - a for a, b in tr.union(
        (a, b) for name, a, b in _window_ops(rec, dev) if pick(name))) * 1e-9


def scope_seconds(rec: dict, scopes: dict[str, str], scope: str,
                  dev: str = "0") -> float:
    """Device seconds in the window of the ops whose own ``op_name`` lies
    under ``scope`` (see ``under``)."""
    return _seconds(rec, dev, lambda name: under(scopes.get(name, ""), scope))


def touch_seconds(rec: dict, contents: dict[str, frozenset[str]],
                  scope: str, dev: str = "0") -> float:
    """Device seconds in the window of the ops with any instruction under
    ``scope``, fused ones included (see ``op_contents``), each op whole."""
    return _seconds(rec, dev, lambda name: any(
        under(o, scope) for o in contents.get(name, ())))


@functools.lru_cache(maxsize=4)
def _cnn_hlo(config_json: str, batch: int) -> str:
    import jax

    from chipbench.families.cnn_infer import images, program
    c = json.loads(config_json)
    _, _, init, fwd = program(c)
    key = jax.random.PRNGKey(0)
    variables = jax.eval_shape(init, key)
    x = jax.eval_shape(
        lambda k: images(k, {"staged_batches": 1, "batch": batch}, c)[0], key)
    # The persistent cache keys programs without their metadata, so a
    # cached executable keeps the scope names of whichever build compiled
    # the same code first. Keyed with it, the text read here is this
    # build's; its instructions are those of the executable the window
    # ran, whichever build compiled that.
    flag = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        return fwd.lower(variables, x).compile().as_text()
    finally:
        jax.config.update(flag, was)


def cnn_hlo(data: dict) -> str:
    """The compiled text of the CNN forward a ``cnn_infer`` cell's window
    ran, as this build names its ops."""
    return _cnn_hlo(json.dumps(data["config"], sort_keys=True),
                    int(data["traffic"]["batch"]))


def cnn_scopes(data: dict) -> dict[str, str]:
    """The op scopes of the CNN forward a ``cnn_infer`` cell's window ran."""
    return op_scopes(cnn_hlo(data))


def cnn_gate_seconds(data: dict, *, fused: bool = False) -> float | None:
    """Device seconds of the Zebra sites (ops under ``zebra.<site>``) in
    the traced window of a ``cnn_infer`` cell; with ``fused``, of every op
    that runs any gate instruction, counted whole. None where the record
    is not the program's or the program names no site."""
    hlo = cnn_hlo(data)
    scopes = op_scopes(hlo)
    if not covers(data["trace"], scopes):
        return None
    gate = (touch_seconds(data["trace"], op_contents(hlo), "zebra") if fused
            else scope_seconds(data["trace"], scopes, "zebra"))
    return gate if gate > 0 else None
