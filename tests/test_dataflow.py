"""The interprocedural dataflow engine analyzed: every OPS6xx/7xx/8xx
rule must catch its planted bug and stay quiet on the clean twin —
including the exact PR 8 donation-aliasing shape (np.load → device_put →
donating step; np.asarray-of-device-buffer → checkpoint save), caught
purely statically: the analyzer parses, it never imports or executes,
so no fixture here ever runs a line of JAX.

Fixture modules are inline source strings, each pair differing only in
the planted defect. The package-level gates at the bottom run the full
engine over the real tree (empty baseline) and prove byte-identical
output across runs.
"""

import json
import os

from paddle_operator_tpu.analysis import dataflow, engine
from paddle_operator_tpu.analysis.ops6xx import make_passes as ownership
from paddle_operator_tpu.analysis.ops7xx import make_passes as mesh
from paddle_operator_tpu.analysis.ops8xx import make_passes as transfers

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def rules_of(findings):
    return {f.rule for f in findings}


def run6(src, path="fixture.py"):
    return dataflow.analyze_source(src, ownership(), path)


def run7(src, path="fixture.py"):
    return dataflow.analyze_source(src, mesh(), path)


def run8(src, path="fixture.py"):
    return dataflow.analyze_source(src, transfers(), path)


# ---------------------------------------------------------------------------
# OPS601 — the PR 8 donation-aliasing regression, statically
# ---------------------------------------------------------------------------

# np.load in one function, device_put in a second, the donating step
# two calls away: no single function contains the bug — the syntactic
# passes (OPS1xx-5xx) cannot see it, the summaries do.
PR8_DONATION_PLANT = '''
import numpy as np
import jax


def restore(path):
    return np.load(path)                 # zero-copy host buffer


def place(tree):
    return jax.device_put(tree)          # aliases the numpy memory (CPU)


def train(path, batches):
    state = place(restore(path))
    step = jax.jit(lambda s, b: (s, s), donate_argnums=(0,))
    for b in batches:
        state, metrics = step(state, b)  # donates the aliased buffer
    return state
'''

# the clean twin IS the PR 8 fix: materialize into runtime-owned buffers
# through a non-donating jit identity before the state enters the step
PR8_DONATION_CLEAN = PR8_DONATION_PLANT.replace(
    "    state = place(restore(path))",
    """    state = place(restore(path))
    state = jax.jit(lambda t: t)(state)   # owned per-device copies""")

# owned host copies on the way in also clean it
PR8_DONATION_CLEAN_HOST = PR8_DONATION_PLANT.replace(
    "    return np.load(path)                 # zero-copy host buffer",
    "    return np.array(np.load(path))       # owned host copy")


def test_ops601_catches_pr8_donation_aliasing_interprocedurally():
    findings = run6(PR8_DONATION_PLANT, "fixture_pr8.py")
    assert rules_of(findings) == {"OPS601"}
    f = findings[0]
    assert "alias" in f.message
    # provenance points back at the buffer's birth
    assert "np.load" in f.message or "device_put" in f.message


def test_ops601_clean_on_materialized_state():
    assert run6(PR8_DONATION_CLEAN, "fixture_pr8_clean.py") == []


def test_ops601_clean_on_owned_host_copy():
    assert run6(PR8_DONATION_CLEAN_HOST, "fixture_pr8_host.py") == []


# donating builder returned across modules-worth of calls: the donation
# signature rides the summary of the builder's RETURN value
BUILDER_PLANT = '''
import numpy as np
import jax


def build_step():
    return jax.jit(lambda s, b: s, donate_argnums=(0,))


def helper(state, b):
    step = build_step()
    return step(state, b)


def outer(path, b):
    s = jax.device_put(np.load(path))
    return helper(s, b)                  # donation two calls away
'''


def test_ops601_donation_signature_propagates_through_summaries():
    findings = run6(BUILDER_PLANT, "fixture_builder.py")
    assert rules_of(findings) == {"OPS601"}


# ---------------------------------------------------------------------------
# OPS602 — use-after-donate
# ---------------------------------------------------------------------------

UAD_PLANT = '''
import jax


def train(state, batches):
    step = jax.jit(lambda s, b: s, donate_argnums=(0,))
    out = []
    for b in batches:
        out.append(step(state, b))       # state never rebound: dead tree
    return out
'''

UAD_CLEAN = '''
import jax


def train(state, batches):
    step = jax.jit(lambda s, b: s, donate_argnums=(0,))
    for b in batches:
        state = step(state, b)           # rebound every step
    return state
'''


def test_ops602_catches_use_after_donate_in_loop():
    findings = run6(UAD_PLANT, "fixture_uad.py")
    assert "OPS602" in rules_of(findings)


def test_ops602_clean_when_state_rebound():
    assert run6(UAD_CLEAN, "fixture_uad_clean.py") == []


# ---------------------------------------------------------------------------
# OPS603 — checkpoint snapshots from unowned device bytes
# ---------------------------------------------------------------------------

SNAPSHOT_PLANT = '''
import numpy as np
import jax.numpy as jnp


def persist(path, arr):
    np.save(path, arr)


def snapshot(path, state):
    host = np.asarray(state)             # zero-copy view of device bytes
    persist(path, host)


def run(path):
    state = jnp.ones((4,))
    snapshot(path, state)
'''

SNAPSHOT_CLEAN = SNAPSHOT_PLANT.replace(
    "    host = np.asarray(state)             # zero-copy view of device bytes",
    "    host = np.array(state)               # owned snapshot")

# checkpoint.py's actual pattern: copy only when the view does not own
# its memory. Branch joins intersect hazard tags (must-analysis), so
# the conditional copy is recognized as cleansing.
OWNED_HOST_PATTERN = '''
import numpy as np
import jax.numpy as jnp


def owned_host(arr):
    a = np.asarray(arr)
    if not a.flags["OWNDATA"]:
        a = np.array(a)
    return a


def save(path, state):
    np.save(path, owned_host(state))


def run(path):
    save(path, jnp.ones((8,)))
'''


def test_ops603_catches_unowned_snapshot_two_calls_from_sink():
    findings = run6(SNAPSHOT_PLANT, "fixture_snap.py")
    assert rules_of(findings) == {"OPS603"}


def test_ops603_clean_on_owned_copy():
    assert run6(SNAPSHOT_CLEAN, "fixture_snap_clean.py") == []


def test_ops603_clean_on_owned_host_conditional_copy_pattern():
    assert run6(OWNED_HOST_PATTERN, "fixture_owned_host.py") == []


# ---------------------------------------------------------------------------
# OPS7xx — mesh / collective consistency
# ---------------------------------------------------------------------------

AXIS_TYPO = '''
import jax
from jax import lax
from paddle_operator_tpu.parallel import make_mesh


def build():
    return make_mesh({"dp": 4, "tp": 2})


def inside(x):
    return lax.psum(x, "dpp")            # typo: no such axis anywhere
'''


def test_ops701_catches_collective_axis_typo():
    findings = run7(AXIS_TYPO, "fixture_axis.py")
    assert rules_of(findings) == {"OPS701"}
    assert findings[0].symbol == "psum.dpp"


def test_ops701_clean_on_defined_axis():
    clean = AXIS_TYPO.replace('"dpp"', '"dp"')
    assert run7(clean, "fixture_axis_clean.py") == []


WRONG_MESH = '''
from jax.sharding import NamedSharding, PartitionSpec as P
from paddle_operator_tpu.parallel import make_mesh


def a_mesh():
    return make_mesh({"dp": 2, "tp": 4})


def b_mesh():
    return make_mesh({"ep": 8})


def place(x):
    mesh = a_mesh()
    return NamedSharding(mesh, P("ep", None))   # ep exists — elsewhere
'''


def test_ops702_axis_known_globally_but_not_on_this_mesh():
    findings = run7(WRONG_MESH, "fixture_wrong_mesh.py")
    assert rules_of(findings) == {"OPS702"}
    assert "not an axis of the mesh" in findings[0].message


def test_ops702_clean_when_spec_matches_its_mesh():
    clean = WRONG_MESH.replace('P("ep", None)', 'P("dp", None)')
    assert run7(clean, "fixture_mesh_ok.py") == []


def test_ops702_rule_tables_are_exempt():
    # (regex, P(...)) tables are mesh-tolerant by contract: named()
    # drops axes the target mesh lacks, one table serves many meshes
    table = '''
from jax.sharding import PartitionSpec as P
from paddle_operator_tpu.parallel import make_mesh


def build():
    return make_mesh({"dp": 2})


def rules():
    return [
        (r"head/kernel", P(None, "nonexistent_axis")),
    ]
'''
    assert run7(table, "fixture_table.py") == []


ARITY_PLANT = '''
import functools
import jax
from jax.sharding import PartitionSpec as P
from paddle_operator_tpu.parallel import make_mesh


def outer():
    mesh = make_mesh({"dp": 8})

    @functools.partial(jax.shard_map, mesh=mesh,
                       in_specs=(P(), P(), P()), out_specs=P())
    def run(a, b):                       # 2 params, 3 specs
        return a + b

    return run
'''


def test_ops703_catches_spec_arity_mismatch():
    findings = run7(ARITY_PLANT, "fixture_arity.py")
    assert rules_of(findings) == {"OPS703"}


def test_ops703_clean_on_matching_arity():
    clean = ARITY_PLANT.replace("in_specs=(P(), P(), P())",
                                "in_specs=(P(), P())")
    assert run7(clean, "fixture_arity_clean.py") == []


# ---------------------------------------------------------------------------
# OPS801 — blocking transfers in step loops
# ---------------------------------------------------------------------------

HOT_PLANT = '''
import jax


def loop(state, batches):
    step = jax.jit(lambda s, b: (s, s))
    losses = []
    for b in batches:
        state, m = step(state, b)
        losses.append(float(m))          # blocking D2H per step
    return losses
'''

HOT_DEFERRED = HOT_PLANT.replace(
    "        losses.append(float(m))          # blocking D2H per step",
    "        losses.append(m)                 # deferred: read after loop")

HOT_EXIT_EXEMPT = '''
import jax
import numpy as np


def loop(state, batches):
    step = jax.jit(lambda s, b: (s, s))
    for b in batches:
        state, m = step(state, b)
        if b is None:
            host = np.asarray(m)         # loop exits right after: exempt
            return host
    return state
'''

HOT_SYNC_OK = '''
import jax


def bench(state, batches):
    step = jax.jit(lambda s, b: (s, s))
    for b in batches:
        state, m = step(state, b)
        jax.block_until_ready(state)     # explicit sync: sanctioned
    return state
'''


def test_ops801_catches_float_per_step():
    findings = run8(HOT_PLANT, "fixture_hot.py")
    assert rules_of(findings) == {"OPS801"}


def test_ops801_clean_when_deferred():
    assert run8(HOT_DEFERRED, "fixture_hot_clean.py") == []


def test_ops801_loop_exiting_block_is_exempt():
    assert run8(HOT_EXIT_EXEMPT, "fixture_hot_exit.py") == []


def test_ops801_explicit_block_until_ready_not_flagged():
    assert run8(HOT_SYNC_OK, "fixture_hot_sync.py") == []


# ---------------------------------------------------------------------------
# the real tree: every family clean against the EMPTY committed baseline
# ---------------------------------------------------------------------------

def test_real_tree_clean_and_baseline_empty():
    """The acceptance gate in-suite: OPS6xx/7xx/8xx (plus every opslint
    family and the OPS001 audit) run clean over the package + scripts,
    and the committed baseline holds zero entries."""
    from paddle_operator_tpu.analysis import opslint

    findings = engine.run_all(
        [os.path.join(REPO, "paddle_operator_tpu"),
         os.path.join(REPO, "scripts")],
        root=REPO,
        axis_paths=[os.path.join(REPO, "tests"),
                    os.path.join(REPO, "examples")])
    assert findings == [], "\n".join(f.render() for f in findings)
    baseline = opslint.load_baseline(
        os.path.join(REPO, "opslint_baseline.json"))
    assert baseline == {}, "baseline must stay empty (fix, don't accept)"


def test_analysis_is_deterministic(tmp_path):
    """Two runs over an unchanged tree produce byte-identical reports
    (fingerprints included): no dict-order or path-order leaks."""
    import scripts.analyze_all as aa

    # a self-contained scope: suppression pragmas elsewhere are only
    # "live" when their whole dataflow context (the package) is parsed,
    # so partial scopes must not include files carrying them
    scope = [os.path.join(REPO, "paddle_operator_tpu", "sched"),
             os.path.join(REPO, "paddle_operator_tpu", "analysis"),
             os.path.join(REPO, "paddle_operator_tpu", "k8s")]
    outs = []
    for i in (1, 2):
        out = str(tmp_path / ("report_%d.json" % i))
        rc = aa.main(scope + ["--no-baseline", "--skip-tools",
                              "--out", out, "--budget-seconds", "0"])
        assert rc == 0
        with open(out, "rb") as fh:
            payload = json.loads(fh.read())
        # elapsed wall time legitimately differs run to run; everything
        # else must be identical bytes
        payload.pop("elapsed_seconds")
        outs.append(json.dumps(payload, sort_keys=True))
    assert outs[0] == outs[1]
