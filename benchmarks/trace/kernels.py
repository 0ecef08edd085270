"""How operations are named on a TPU's "XLA Ops" line, and how the
program's Pallas kernels are told apart there.

An event's name is the whole HLO instruction:

    %fusion.1089 = (f32[64,128]{1,0:T(8,128)S(1)}, ...) fusion(...), kind=kOutput, calls=...
    %step.15 = f32[32,80,128]{...} custom-call(s32[17,16]{...} %copy-done.54, ...),
        custom_call_target="tpu_custom_call", ...

A Pallas kernel is a `tpu_custom_call`, and its instruction carries the
name of whatever traced function it was inlined into (`step`, `checkpoint`,
`closed_call`, `rematted_computation`), not the kernel's: the program gives
its `pallas_call`s no name (PERF.md, Open questions: stable kernel names).
Until it does, the kernels are told apart by their operands, as a chip trace
of each showed them (PR 22):

    ragged paged attention   first operand is the int32 page table
    flash attention forward  4 operands (q, k, v, mask), float first
    flash attention backward 7 operands (q, k, v, o-stats..., do, mask): two
                             calls, dq and dk/dv, make one backward pass

`short_name()` is what the reduction keys operations by: a kernel becomes
its kind alone ("pallas:ragged"), so that the calls of every layer add up;
any other operation keeps its instruction name without its number, its
opcode and its result shape without layouts ("psum all-reduce
f32[8,1024,1280]"), so that the same work in every layer adds up too.
"""
import functools
import re

PALLAS = 'custom_call_target="tpu_custom_call"'
RAGGED, FLASH_FORWARD, FLASH_BACKWARD, OTHER = (
    "pallas:ragged", "pallas:flash_fwd", "pallas:flash_bwd", "pallas:other")
FLASH_BACKWARD_CALLS = 2

_LAYOUT = re.compile(r"\{[^{}]*\}")
_INSTR = re.compile(r"^%?([\w.\-]+) = (\(.*?\)|\S+) ([\w\-]+)\(")
_NUMBER = re.compile(r"\.\d+$")
_OPERANDS = re.compile(r"custom-call\((.*?)\), custom_call_target")


def pallas_kind(hlo):
    """Which of the program's kernels a `tpu_custom_call` instruction is."""
    m = _OPERANDS.search(_LAYOUT.sub("", hlo))
    if m is None:
        return OTHER
    operands = m.group(1).split(", ")
    if operands[0].startswith("s32["):
        return RAGGED
    if len(operands) == 4:
        return FLASH_FORWARD
    if len(operands) == 7:
        return FLASH_BACKWARD
    return OTHER


@functools.lru_cache(maxsize=65536)
def short_name(hlo):
    if PALLAS in hlo:
        return pallas_kind(hlo)
    m = _INSTR.match(_LAYOUT.sub("", hlo))
    if m is None:
        return hlo[:100]
    instr, shape, opcode = m.groups()
    # without the instruction's number: the 16 pool transposes of a step,
    # or the 7 all-reduces of a scanned block, then add up under one name
    return f"{_NUMBER.sub('', instr)} {opcode} {shape}"[:120]


def is_ragged(name):
    return name == RAGGED


def is_flash(name):
    return name in (FLASH_FORWARD, FLASH_BACKWARD)


def is_flash_forward(name):
    return name == FLASH_FORWARD


def is_flash_backward(name):
    return name == FLASH_BACKWARD
