"""Unit tests for the dptpu.analysis lint engine and every rule —
positive (a violating snippet is found), negative (idiomatic code is
not), and pragma-suppressed (a reasoned pragma silences exactly that
line and lands in the suppression census) — plus the LOCKED
actionable-message contract: every finding names its rule, its
file:line, and the pragma syntax that would suppress it.

Pure stdlib (the lint engine imports no jax/numpy) — tier-1 fast.
"""

import textwrap

import pytest

from dptpu.analysis import KNOB_REGISTRY, lint_source
from dptpu.analysis.lint import RepoContext, iter_rules
from dptpu.envknob import env_str


def _lint(path, src, readme=None, only=None):
    repo = RepoContext(root=None, readme_text=readme, knobs=KNOB_REGISTRY)
    return lint_source(path, textwrap.dedent(src), repo, only_rules=only)


def _rules_of(findings):
    return [f.rule for f in findings]


# ------------------------------------------------------- message contract


def test_finding_message_contract_is_locked():
    """Rule name, file:line, and the pragma syntax in EVERY finding."""
    findings, _ = _lint(
        "dptpu/train/fit.py",
        'import os\nv = os.environ.get("DPTPU_ACCUM", "1")\n',
    )
    assert findings, "seeded violation must be found"
    for f in findings:
        msg = f.format()
        assert f.rule in msg
        assert f"{f.path}:{f.line}" in msg
        assert f"# dptpu: allow-{f.rule}(" in msg


def test_unsuppressible_findings_do_not_advertise_a_pragma():
    """The 'pragma' meta-rule cannot be pragma'd away — its messages
    must not tell the user to try (following a bogus hint would just
    mint an unknown-rule finding)."""
    findings, _ = _lint(
        "dptpu/train/step.py",
        "x = 1  # dptpu: allow-host-sync no parens\n",
    )
    assert _rules_of(findings) == ["pragma"]
    msg = findings[0].format()
    assert "not suppressible" in msg
    assert "# dptpu: allow-pragma(" not in msg


def test_every_rule_has_a_doc():
    rules = iter_rules()
    assert {r.name for r in rules} >= {
        "knob-contract", "determinism", "host-sync", "shm-hygiene",
        "shard-map",
    }
    assert all(r.doc for r in rules)


# --------------------------------------------------------- knob-contract


def test_knob_raw_environ_get_flagged():
    findings, _ = _lint(
        "dptpu/serve/engine.py",
        'import os\nx = os.environ.get("DPTPU_SERVE_SLOTS", "4")\n',
        only=["knob-contract"],
    )
    assert _rules_of(findings) == ["knob-contract"]
    assert "envknob" in findings[0].message


def test_knob_os_getenv_and_setdefault_flagged():
    findings, _ = _lint(
        "dptpu/train/fit.py",
        'import os\n'
        'a = os.getenv("DPTPU_ACCUM", "1")\n'
        'b = os.environ.setdefault("DPTPU_ACCUM", "1")\n'
        'c = os.environ.setdefault("JAX_PLATFORMS", "cpu")\n',
        only=["knob-contract"],
    )
    assert [f.line for f in findings] == [2, 3]


def test_knob_raw_subscript_read_flagged_but_write_allowed():
    findings, _ = _lint(
        "scripts/run_x.py",
        'import os\n'
        'os.environ["DPTPU_FAULT"] = "spec"\n'   # write: a bench arming
        'v = os.environ["DPTPU_FAULT"]\n',       # load: a raw read
        only=["knob-contract"],
    )
    assert len(findings) == 1
    assert findings[0].line == 3


def test_knob_undeclared_literal_flagged_and_declared_ok():
    findings, _ = _lint(
        "dptpu/train/fit.py",
        'K = "DPTPU_TOTALLY_NEW_KNOB"\nG = "DPTPU_ACCUM"\n',
        only=["knob-contract"],
    )
    assert len(findings) == 1
    assert "DPTPU_TOTALLY_NEW_KNOB" in findings[0].message


def test_knob_prefix_literal_matches_registry():
    findings, _ = _lint(
        "dptpu/train/fit.py",
        'P = "DPTPU_OBS_"\nQ = "DPTPU_NOPE_"\n',
        only=["knob-contract"],
    )
    assert len(findings) == 1
    assert "DPTPU_NOPE_" in findings[0].message


def test_knob_envknob_helpers_are_clean():
    findings, _ = _lint(
        "dptpu/train/fit.py",
        'from dptpu.envknob import env_int\n'
        'v = env_int("DPTPU_ACCUM", 1)\n',
        only=["knob-contract"],
    )
    assert findings == []


def test_knob_registry_readme_cross_check():
    src = open("dptpu/analysis/knobs.py", encoding="utf-8").read()
    # a README documenting everything -> clean
    full_readme = "\n".join(KNOB_REGISTRY)
    findings, _ = _lint("dptpu/analysis/knobs.py", src,
                        readme=full_readme, only=["knob-contract"])
    assert findings == []
    # drop one non-internal knob from the docs -> exactly that finding
    partial = "\n".join(k for k in KNOB_REGISTRY if k != "DPTPU_ACCUM")
    findings, _ = _lint("dptpu/analysis/knobs.py", src,
                        readme=partial, only=["knob-contract"])
    assert len(findings) == 1
    assert "DPTPU_ACCUM" in findings[0].message
    # internal sentinels never require README docs
    partial = "\n".join(
        k for k in KNOB_REGISTRY if k != "DPTPU_NUMERICS_CHILD"
    )
    findings, _ = _lint("dptpu/analysis/knobs.py", src,
                        readme=partial, only=["knob-contract"])
    assert findings == []
    # boundary match: DPTPU_SP_MODE being documented must NOT count as
    # documentation for its prefix DPTPU_SP
    partial = "\n".join(k for k in KNOB_REGISTRY if k != "DPTPU_SP")
    assert "DPTPU_SP_MODE" in partial
    findings, _ = _lint("dptpu/analysis/knobs.py", src,
                        readme=partial, only=["knob-contract"])
    assert len(findings) == 1
    assert "DPTPU_SP " in findings[0].message + " "


# ---------------------------------------------------------- determinism


@pytest.mark.parametrize("snippet,needle", [
    ("import time\nts = time.time()\n", "wall-clock"),
    ("import os\nb = os.urandom(8)\n", "urandom"),
    ("import random\nx = random.random()\n", "process-global"),
    ("import random\nr = random.Random()\n", "without a seed"),
    ("import numpy as np\nx = np.random.randint(0, 4)\n", "global RNG"),
    ("import numpy as np\nr = np.random.RandomState()\n",
     "without a seed"),
    ("for x in {1, 2}:\n    pass\n", "set"),
    ("out = [x for x in set(range(3))]\n", "set"),
])
def test_determinism_positive(snippet, needle):
    findings, _ = _lint("dptpu/data/sampler.py", snippet,
                        only=["determinism"])
    assert _rules_of(findings) == ["determinism"], snippet
    assert needle in findings[0].message


def test_determinism_seeded_and_monotonic_are_clean():
    findings, _ = _lint(
        "dptpu/resilience/faults.py",
        "import random\nimport time\nimport numpy as np\n"
        "r = random.Random(7)\n"
        "g = np.random.RandomState(0)\n"
        "d = np.random.default_rng(3)\n"
        "t = time.monotonic()\n"
        "for x in sorted({1, 2}):\n    pass\n",
        only=["determinism"],
    )
    assert findings == []


def test_determinism_scoped_to_bit_identity_surfaces():
    findings, _ = _lint(
        "dptpu/serve/engine.py", "import time\nts = time.time()\n",
        only=["determinism"],
    )
    assert findings == []


# ------------------------------------------------------------ host-sync


@pytest.mark.parametrize("snippet,needle", [
    ("import jax\nv = jax.device_get(x)\n", "device_get"),
    ("v = arr.item()\n", ".item()"),
    ("arr.block_until_ready()\n", "dispatch queue"),
    ("import numpy as np\nv = np.asarray(arr)\n", "host"),
    ("v = float(arr)\n", "sync"),
])
def test_host_sync_positive_in_step(snippet, needle):
    findings, _ = _lint("dptpu/train/step.py", snippet,
                        only=["host-sync"])
    assert _rules_of(findings) == ["host-sync"], snippet
    assert needle in findings[0].message


def test_host_sync_scoped_to_hot_files_and_prefetcher():
    # not a hot file -> clean
    findings, _ = _lint("dptpu/obs/report.py",
                        "v = arr.item()\n", only=["host-sync"])
    assert findings == []
    # loader.py outside DevicePrefetcher -> clean; inside -> finding
    src = """\
    import jax

    def worker():
        return jax.device_get(x)

    class DevicePrefetcher:
        def go(self):
            return jax.device_get(x)
    """
    findings, _ = _lint("dptpu/data/loader.py", src, only=["host-sync"])
    assert len(findings) == 1
    assert findings[0].line == 8


def test_host_sync_float_not_flagged_in_loop():
    # loop.py converts ALREADY-FETCHED host scalars with float(); the
    # device_get sites are the policed sync points there
    findings, _ = _lint("dptpu/train/loop.py",
                        'v = float(m["loss"])\n', only=["host-sync"])
    assert findings == []


# ---------------------------------------------------------- shm-hygiene


def test_shm_direct_creation_flagged():
    findings, _ = _lint(
        "dptpu/data/newring.py",
        "from multiprocessing import shared_memory\n"
        "s = shared_memory.SharedMemory(name='x', create=True, size=4)\n",
        only=["shm-hygiene"],
    )
    assert _rules_of(findings) == ["shm-hygiene"]
    assert "create_named_segment" in findings[0].message


def test_shm_census_prefix_enforced():
    findings, _ = _lint(
        "dptpu/data/newring.py",
        "from dptpu.data.shm_cache import create_named_segment\n"
        "a = create_named_segment('dptpu_ring', 64)\n"
        "b = create_named_segment('dptpu_rogue', 64)\n",
        only=["shm-hygiene"],
    )
    assert len(findings) == 1
    assert findings[0].line == 3
    assert "census" in findings[0].message


def test_shm_module_const_prefix_resolves():
    findings, _ = _lint(
        "dptpu/serve/newstage.py",
        "from dptpu.data.shm_cache import create_named_segment\n"
        "SEGMENT_PREFIX = 'dptpu_serve'\n"
        "s = create_named_segment(SEGMENT_PREFIX, 64)\n",
        only=["shm-hygiene"],
    )
    assert findings == []


# ------------------------------------------------------------ shard-map


def test_shard_map_raw_call_flagged_nocheck_wrapper_clean():
    src = """\
    from jax import shard_map

    def shard_map_nocheck(f, mesh, in_specs, out_specs):
        return shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

    def make_step(mesh):
        return shard_map(lambda s: s, mesh=mesh, in_specs=(),
                         out_specs=())
    """
    findings, _ = _lint("dptpu/parallel/newstep.py", src,
                        only=["shard-map"])
    assert len(findings) == 1
    assert findings[0].line == 8
    assert "check_vma=False" in findings[0].message


def test_shard_map_axis_names_threading():
    src = """\
    from dptpu.train.step import train_step_body

    def good(state, batch):
        return train_step_body(state, batch, axis_names=("data",))

    def bad(state, batch):
        return train_step_body(state, batch)
    """
    findings, _ = _lint("dptpu/parallel/newstep.py", src,
                        only=["shard-map"])
    assert len(findings) == 1
    assert findings[0].line == 7
    assert "axis_names" in findings[0].message


# ----------------------------------------------------- pragma mechanics


def test_pragma_suppresses_and_is_censused():
    findings, sups = _lint(
        "dptpu/train/step.py",
        "v = arr.item()  "
        "# dptpu: allow-host-sync(measured harness needs the sync)\n",
    )
    assert findings == []
    assert len(sups) == 1
    assert sups[0].rule == "host-sync"
    assert sups[0].reason == "measured harness needs the sync"


def test_pragma_reason_is_mandatory():
    findings, sups = _lint(
        "dptpu/train/step.py",
        "v = arr.item()  # dptpu: allow-host-sync()\n",
    )
    rules = _rules_of(findings)
    # the empty-reason pragma suppresses nothing AND is itself flagged
    assert "pragma" in rules and "host-sync" in rules
    assert sups == []


def test_pragma_unknown_rule_and_unused_are_findings():
    findings, _ = _lint(
        "dptpu/train/step.py",
        "x = 1  # dptpu: allow-no-such-rule(because)\n"
        "y = 2  # dptpu: allow-host-sync(nothing here syncs)\n",
    )
    msgs = [f.message for f in findings]
    assert any("unknown rule" in m for m in msgs)
    assert any("unused pragma" in m for m in msgs)


def test_pragma_malformed_flagged_but_syntax_docs_are_not():
    findings, _ = _lint(
        "dptpu/train/step.py",
        "x = 1  # dptpu: allow-host-sync no parens\n"
        '"""the syntax is # dptpu: allow-<rule>(<reason>)"""\n',
    )
    assert _rules_of(findings) == ["pragma"]
    assert "malformed" in findings[0].message


def test_pragma_only_suppresses_its_own_rule_and_line():
    findings, _ = _lint(
        "dptpu/train/step.py",
        "v = arr.item()  # dptpu: allow-determinism(wrong rule)\n",
    )
    rules = _rules_of(findings)
    assert "host-sync" in rules          # still found
    assert "pragma" in rules             # and the pragma is unused


# ------------------------------------------------------------- env_str


def test_env_str_contract():
    assert env_str("DPTPU_X", None, environ={}) is None
    assert env_str("DPTPU_X", "d", environ={"DPTPU_X": ""}) == "d"
    assert env_str("DPTPU_X", "d", environ={"DPTPU_X": "  v  "}) == "v"
