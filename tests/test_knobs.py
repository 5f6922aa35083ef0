"""Error paths of the typed knob registry (tpustack/utils/knobs.py).

PR 8 tested the happy path (typed reads, defaults, the generated doc
table); this suite pins the failure contract: a malformed value produces
a clear error NAMING the knob, an undeclared read raises immediately, and
a wrong-typed read is a programming error — never a silent default.
"""

from __future__ import annotations

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tpustack.utils import knobs  # noqa: E402


# ----------------------------------------------------------- malformed values
def test_malformed_int_names_the_knob():
    with pytest.raises(ValueError) as ei:
        knobs.get_int("LLM_CTX", env={"LLM_CTX": "four-thousand"})
    msg = str(ei.value)
    assert "LLM_CTX" in msg and "four-thousand" in msg
    assert "integer" in msg


def test_malformed_float_names_the_knob():
    with pytest.raises(ValueError) as ei:
        knobs.get_float("TPUSTACK_DRAIN_TIMEOUT_S",
                        env={"TPUSTACK_DRAIN_TIMEOUT_S": "30s"})
    msg = str(ei.value)
    assert "TPUSTACK_DRAIN_TIMEOUT_S" in msg and "30s" in msg
    assert "number" in msg


def test_malformed_bool_names_the_knob_and_the_accepted_spellings():
    with pytest.raises(ValueError) as ei:
        knobs.get_bool("TPUSTACK_PREFIX_CACHE",
                       env={"TPUSTACK_PREFIX_CACHE": "enabled"})
    msg = str(ei.value)
    assert "TPUSTACK_PREFIX_CACHE" in msg and "enabled" in msg
    # the error teaches the accepted spellings — an operator fixing a
    # manifest at 3am must not have to read the source
    assert "1/true/yes/on" in msg and "0/false/no/off" in msg


def test_float_accepts_int_spelling_and_int_rejects_float_spelling():
    assert knobs.get_float("TPUSTACK_DRAIN_TIMEOUT_S",
                           env={"TPUSTACK_DRAIN_TIMEOUT_S": "45"}) == 45.0
    with pytest.raises(ValueError):
        knobs.get_int("LLM_CTX", env={"LLM_CTX": "4096.0"})


def test_blank_and_whitespace_values_fall_back_to_defaults():
    # a manifest stub with `value: ""` must not flip defaults or crash
    assert knobs.get_int("LLM_CTX", env={"LLM_CTX": ""}) == 4096
    assert knobs.get_float("TPUSTACK_DRAIN_TIMEOUT_S",
                           env={"TPUSTACK_DRAIN_TIMEOUT_S": "  "}) == 30.0
    assert knobs.get_bool("TPUSTACK_PREFIX_CACHE",
                          env={"TPUSTACK_PREFIX_CACHE": ""}) is True


def test_bool_spellings_case_insensitive():
    for raw, want in (("TRUE", True), ("Yes", True), ("oN", True),
                      ("FALSE", False), ("No", False), ("0", False)):
        assert knobs.get_bool("TPUSTACK_PREFIX_CACHE",
                              env={"TPUSTACK_PREFIX_CACHE": raw}) is want


# ------------------------------------------------------------ undeclared reads
@pytest.mark.parametrize("getter", [knobs.get_str, knobs.get_int,
                                    knobs.get_float, knobs.get_bool])
def test_undeclared_knob_raises_keyerror_naming_the_registry(getter):
    with pytest.raises(KeyError) as ei:
        getter("TPUSTACK_NO_SUCH_KNOB", env={})
    msg = str(ei.value)
    assert "TPUSTACK_NO_SUCH_KNOB" in msg
    # the error points at where to declare it and the enforcing lint
    assert "knobs.py" in msg and "TPL402" in msg


def test_wrong_typed_read_is_a_typeerror():
    # LLM_CTX is declared int; reading it as anything else is a bug in
    # the CALLER, reported as such (not a parse error)
    with pytest.raises(TypeError) as ei:
        knobs.get_str("LLM_CTX", env={"LLM_CTX": "4096"})
    assert "LLM_CTX" in str(ei.value) and "int" in str(ei.value)
    with pytest.raises(TypeError):
        knobs.get_bool("LLM_PRESET", env={})


# --------------------------------------------------------- declaration guards
def test_duplicate_declaration_rejected():
    with pytest.raises(ValueError):
        knobs._declare("LLM_CTX", int, 1, "dup")


def test_declaration_type_and_default_validated():
    with pytest.raises(TypeError):
        knobs._declare("TPUSTACK_TEST_BAD_TYPE", list, [], "bad type")
    with pytest.raises(TypeError):
        knobs._declare("TPUSTACK_TEST_BAD_DEFAULT", int, "7", "bad default")


def test_environment_wins_over_default_and_env_mapping_is_isolated():
    # the env= injection contract: reads never touch os.environ when a
    # mapping is passed (component test isolation)
    os.environ.pop("LLM_CTX", None)
    assert knobs.get_int("LLM_CTX", env={"LLM_CTX": "128"}) == 128
    assert knobs.get_int("LLM_CTX", env={}) == 4096
