"""Default program management (reference: python/paddle/fluid/framework.py
default_main_program :3715, program_guard :3795)."""

from __future__ import annotations

import contextlib

from paddle_tpu.core.program import Program

_main_program = Program()
_startup_program = Program()
_dygraph_mode = False


def default_main_program() -> Program:
    return _main_program


def default_startup_program() -> Program:
    return _startup_program


def switch_main_program(program: Program) -> Program:
    global _main_program
    old = _main_program
    _main_program = program
    return old


def switch_startup_program(program: Program) -> Program:
    global _startup_program
    old = _startup_program
    _startup_program = program
    return old


@contextlib.contextmanager
def program_guard(main_program, startup_program=None):
    old_main = switch_main_program(main_program)
    old_startup = None
    if startup_program is not None:
        old_startup = switch_startup_program(startup_program)
    try:
        yield
    finally:
        switch_main_program(old_main)
        if old_startup is not None:
            switch_startup_program(old_startup)


@contextlib.contextmanager
def name_scope(prefix):
    """Generated names get `prefix/`, and ops appended inside carry the
    scope into the compiled step's metadata (core/program.py
    `op_scope`)."""
    from paddle_tpu import unique_name
    from paddle_tpu.core.program import _NAME_SCOPE

    unique_name._prefix.append(prefix)
    _NAME_SCOPE.append(prefix)
    try:
        yield
    finally:
        _NAME_SCOPE.pop()
        unique_name._prefix.pop()


def in_dygraph_mode() -> bool:
    return _dygraph_mode


@contextlib.contextmanager
def _dygraph_guard(value: bool):
    global _dygraph_mode
    old = _dygraph_mode
    _dygraph_mode = value
    try:
        yield
    finally:
        _dygraph_mode = old
