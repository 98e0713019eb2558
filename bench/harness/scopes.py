"""Attribute a trace's device time to the program's named scopes.

A TPU's op events carry no metadata: each is named by its instruction's
whole HLO text, operands included (``%tanh.1 = f32[64,1024] tanh(...
%pim_matmul_fused_pallas.1)``), so a pattern searched in the text also
matches the ops that read a kernel's output. The scope of an op is
joined through the compiled program instead: ``compiled.as_text()``
gives each instruction's ``op_name`` metadata (``jit(forward)/s0b0c1/
im2col/concatenate``), and an event's own instruction is the text before
``" = "``. A fusion carries the scope of its root op.

Times are clipped to the harness's ``window`` span and averaged over the
devices, as ``trace.reduce_trace`` does.
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, Optional

from harness import trace as trace_mod

UNSCOPED = "unscoped"

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=]+)\s=\s(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([^\s,}]+)")
_OPERAND = re.compile(r"%([^\s,)]+)")


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name (without ``%``) -> its ``op_name``, from a
    compiled program's text.

    The TPU compiler adds instructions without metadata: the chunks of a
    concatenate it splits, the halves of async copies and slices. Such
    an instruction takes the ``op_name`` nearest the root of the
    computation it calls (a fusion's own ops keep theirs), else that of
    its first operand (the buffer a split concatenate's chunk updates,
    the copy an async copy's second half completes). Instructions with
    neither are left out."""
    named: Dict[str, str] = {}
    last: Dict[str, str] = {}    # computation -> op_name nearest its root
    comp = None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                comp = c.group(1)
            continue
        name, rest = m.groups()
        own, calls = _OP_NAME.search(rest), _CALLS.search(rest)
        op = own.group(1) if own else \
            last.get(calls.group(1)) if calls else None
        if op is None:
            first = _OPERAND.search(rest)
            op = named.get(first.group(1)) if first else None
        if op is not None:
            named[name] = op
            if comp is not None:
                last[comp] = op
    return named


def instruction(event_name: str) -> str:
    """An op event's own instruction: its text before ``" = "`` (an HLO
    text line's also, without ``ROOT``)."""
    head = event_name.split(" = ", 1)[0].split()
    return head[-1].lstrip("%") if head else ""


def innermost(op_name: Optional[str], known: Iterable[str]) -> str:
    """The innermost component of ``op_name`` that is in ``known``, or
    ``UNSCOPED``."""
    known = set(known)
    for part in reversed((op_name or "").split("/")):
        if part in known:
            return part
    return UNSCOPED


def op_times(path: str) -> Dict[str, float]:
    """Device seconds per op event name (the instruction's HLO text)
    inside the window span, averaged over the devices."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    window, planes = [], []
    for plane in pd.planes:
        if trace_mod._is_device(plane):
            planes.append(plane)
        elif plane.name.startswith("/host:"):
            window += [trace_mod._event(ev)[1:] for line in plane.lines
                       for ev in line.events
                       if ev.name == trace_mod.WINDOW_SPAN]
    if not window or not planes:
        raise ValueError(f"no window span or no device plane in {path}")
    lo, hi = min(s for s, _ in window), max(e for _, e in window)
    out: Dict[str, float] = {}
    for plane in planes:
        for line in plane.lines:
            if line.name != trace_mod.OPS_LINE:
                continue
            for ev in line.events:
                name, s, e = trace_mod._event(ev)
                iv = trace_mod.clip((s, e), lo, hi)
                if iv is not None:
                    out[name] = out.get(name, 0.0) + iv[1] - iv[0]
    return {k: v / len(planes) for k, v in out.items()}


def scope_times(ops: Dict[str, float], names: Dict[str, str],
                known: Iterable[str]) -> Dict[str, float]:
    """Seconds per innermost known scope of each op's own instruction;
    ops whose instruction has no ``op_name`` in ``names``, or none of
    the ``known`` scopes, go to ``UNSCOPED``."""
    known = set(known)
    out: Dict[str, float] = {}
    for event, t in ops.items():
        key = innermost(names.get(instruction(event)), known)
        out[key] = out.get(key, 0.0) + t
    return out
