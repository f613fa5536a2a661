"""mxlint (``mx.analysis``) — the rules must actually fire.

Per rule R1–R6: one known-violation snippet and one clean
counterexample, linted under a virtual repo path so scoping is
exercised too.  Per HLO check: a synthetic violating artifact and a
clean twin.  Plus the self-scan: the repo itself is clean modulo the
checked-in baseline, and no baseline entry is stale (the ratchet).
"""
import os
import subprocess
import sys

import pytest

from mxnet_tpu.analysis import hlo, lint

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _ids(src, relpath, rule=None):
    diags = lint.lint_source(src, relpath,
                             rules={rule} if rule else None)
    return [d.rule_id for d in diags]


# ----------------------------------------------------------------------
# R1 — coordinated collective launch
# ----------------------------------------------------------------------
R1_BAD = """
from jax import lax
from jax.sharding import PartitionSpec as P

def body(x, axis_name="pp"):
    return lax.psum(x, axis_name)

def apply_batch(x, mesh):
    return _shard_map(body, mesh, (P(),), P())(x)
"""

R1_CLEAN = """
from jax import lax
from jax.sharding import PartitionSpec as P

def body(x, axis_name="pp"):
    return lax.psum(x, axis_name)

def apply_batch(x, mesh):
    def attempt():
        return _shard_map(body, mesh, (P(),), P())(x)
    return coordinated_call(attempt, op="apply_batch")
"""


def test_r1_fires_on_unseamed_launch():
    assert _ids(R1_BAD, "mxnet_tpu/parallel/fx.py") == ["R1"]


def test_r1_clean_when_launch_rides_the_seam():
    assert _ids(R1_CLEAN, "mxnet_tpu/parallel/fx.py") == []


def test_r1_scoped_to_distributed_modules():
    # the same launch outside parallel/kvstore is not R1's business
    assert _ids(R1_BAD, "mxnet_tpu/image/fx.py") == []


# ----------------------------------------------------------------------
# R2 — atomic artifact writes
# ----------------------------------------------------------------------
R2_BAD = """
import json

def dump_report(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)
"""

R2_CLEAN = """
import json, os

def dump_report(path, obj):
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f)
    os.replace(tmp, path)
"""

R2_SUPPRESSED = """
def journal(path, line):
    # mxlint: disable=R2 -- append-only journal; lines self-contained
    with open(path, "a") as f:
        f.write(line)
"""

R2_BARE_SUPPRESS = """
def journal(path, line):
    # mxlint: disable=R2
    with open(path, "a") as f:
        f.write(line)
"""


def test_r2_fires_on_raw_write():
    assert _ids(R2_BAD, "tools/fx.py") == ["R2"]


R2_BAD_FAKE_LINK = """
def dump(path, obj, photos):
    photos.link(obj)
    link(path, obj)
    with open(path, "w") as f:
        f.write(obj)
"""

R2_CLEAN_OS_LINK = """
import json, os

def claim(path, obj):
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(obj, f)
    os.link(tmp, path)
"""


def test_r2_clean_with_replace_commit_point():
    assert _ids(R2_CLEAN, "tools/fx.py") == []


def test_r2_os_link_is_a_commit_point_but_lookalikes_are_not():
    # tmp+os.link (first-writer-wins claim) commits like os.replace...
    assert _ids(R2_CLEAN_OS_LINK, "tools/fx.py") == []
    # ...but a same-named helper or method must not exempt a raw write
    assert _ids(R2_BAD_FAKE_LINK, "tools/fx.py") == ["R2"]


def test_r2_inline_suppression_needs_justification():
    assert _ids(R2_SUPPRESSED, "tools/fx.py") == []
    # a bare disable= is itself flagged — suppressions cannot rot
    assert _ids(R2_BARE_SUPPRESS, "tools/fx.py") == ["MX901"]


# ----------------------------------------------------------------------
# R3 — entry-seam-only retry for mutating ops
# ----------------------------------------------------------------------
R3_BAD = """
def guarded_push(fn, mutating=False):
    return retry_call(fn, op="push", policy=mutating_policy())
"""

R3_BAD_TIMEOUT = """
def guarded(fn):
    return retry_call(fn, op="allreduce",
                      policy=RetryPolicy(timeout=5.0))
"""

R3_CLEAN = """
def guarded_push(fn, mutating=False):
    return retry_call(fn, op="push", policy=entry_only_policy())
"""


def test_r3_fires_on_mutating_retry_without_entry_policy():
    assert _ids(R3_BAD, "mxnet_tpu/kvstore/fx.py") == ["R3"]


def test_r3_fires_on_per_attempt_timeout():
    assert _ids(R3_BAD_TIMEOUT, "mxnet_tpu/kvstore/fx.py") == ["R3"]


def test_r3_clean_with_entry_only_policy():
    assert _ids(R3_CLEAN, "mxnet_tpu/kvstore/fx.py") == []


# ----------------------------------------------------------------------
# R4 — no swallowed coordination aborts
# ----------------------------------------------------------------------
R4_BAD = """
def poll(fn, log):
    try:
        fn()
    except Exception:
        log("oops")
"""

R4_CLEAN = """
def poll(fn, log):
    try:
        fn()
    except Exception:
        log("oops")
        raise
"""


def test_r4_fires_on_swallowing_broad_except():
    assert _ids(R4_BAD, "mxnet_tpu/kvstore/fx.py") == ["R4"]


def test_r4_clean_when_reraising():
    assert _ids(R4_CLEAN, "mxnet_tpu/kvstore/fx.py") == []


# ----------------------------------------------------------------------
# R5 — pure traced step code
# ----------------------------------------------------------------------
R5_BAD = """
import jax

def step(params, x):
    lr = params["lr"].item()
    print("stepping")
    return x * lr

jitted = jax.jit(step)
"""

R5_BAD_STORE = """
import jax

def _build(self):
    def run(x):
        self.handle.data = x
        return x
    def step(x):
        return run(x)
    return jax.jit(step)
"""

R5_CLEAN = """
import jax
import jax.numpy as jnp

def step(params, x):
    return x * jnp.float32(2.0)

jitted = jax.jit(step)
"""


def test_r5_fires_on_host_sync_in_traced_code():
    assert _ids(R5_BAD, "mxnet_tpu/parallel/fx.py") == ["R5", "R5"]


def test_r5_fires_on_attribute_store_in_traced_code():
    # reached transitively: step -> run, both nested helpers
    assert _ids(R5_BAD_STORE, "mxnet_tpu/parallel/fx.py") == ["R5"]


def test_r5_clean_on_pure_step():
    assert _ids(R5_CLEAN, "mxnet_tpu/parallel/fx.py") == []


def test_r5_ignores_untraced_host_code():
    # the same .item() outside any traced function is ordinary host code
    src = "def log_loss(loss):\n    return loss.item()\n"
    assert _ids(src, "mxnet_tpu/parallel/fx.py") == []


# ----------------------------------------------------------------------
# R6 — deterministic tier-1 tests
# ----------------------------------------------------------------------
R6_BAD_TIME = """
import time

def test_fresh():
    assert time.time() > 0
"""

R6_BAD_MODULE_DRAW = """
import numpy as onp

X = onp.random.rand(3)
"""

R6_BAD_UNSEEDED_RS = """
import numpy as onp

def test_x():
    rs = onp.random.RandomState()
"""

R6_CLEAN = """
import numpy as onp

_rs = onp.random.RandomState(7)

def test_x():
    assert _rs.rand(3).shape == (3,)
"""

R6_CONFTEST_BAD = """
import numpy as onp

def seed_fixture():
    seed = onp.random.randint(0, 2 ** 31)
    onp.random.seed(seed)
"""

R6_CONFTEST_CLEAN = """
import numpy as onp

def seed_fixture(seed):
    onp.random.seed(seed)
    return onp.random.randint(0, 2 ** 31)
"""


def test_r6_fires_on_wall_clock():
    assert _ids(R6_BAD_TIME, "tests/fx_test.py") == ["R6"]


def test_r6_sees_from_imports():
    # `from time import time` must be as visible as `import time`
    src = "from time import time\n\ndef test_x():\n    assert time() > 0\n"
    assert _ids(src, "tests/fx_test.py") == ["R6"]
    src = ("from numpy import random\n\nX = random.rand(3)\n")
    assert _ids(src, "tests/fx_test.py") == ["R6"]


def test_r5_sees_from_imports():
    src = ("import jax\nfrom numpy import asarray\n\n"
           "def step(x):\n    return asarray(x)\n\nj = jax.jit(step)\n")
    assert _ids(src, "mxnet_tpu/parallel/fx.py") == ["R5"]


def test_r6_fires_on_module_scope_draw():
    assert _ids(R6_BAD_MODULE_DRAW, "tests/fx_test.py") == ["R6"]


def test_r6_fires_on_unseeded_randomstate():
    assert _ids(R6_BAD_UNSEEDED_RS, "tests/fx_test.py") == ["R6"]


def test_r6_clean_on_seeded_module_rng():
    assert _ids(R6_CLEAN, "tests/fx_test.py") == []


def test_r6_conftest_draw_before_seed():
    # conftest code runs OUTSIDE the autouse seeding fixture: a draw
    # with no earlier seed() in the same function is entropy
    assert _ids(R6_CONFTEST_BAD, "tests/conftest.py") == ["R6"]
    assert _ids(R6_CONFTEST_CLEAN, "tests/conftest.py") == []


# ----------------------------------------------------------------------
# R7 — rank-divergent control flow guarding a collective launch
# ----------------------------------------------------------------------
R7_BAD = """
from jax import lax

def step(x, rank):
    if rank == 0:
        return lax.psum(x, "dp")
    return x
"""

R7_BAD_PROCESS_INDEX = """
import jax

def maybe_sync(comm, x):
    if jax.process_index() == 0:
        comm.allgather(x)
"""

R7_CLEAN_HOIST = """
from jax import lax

def step(x, rank):
    y = lax.psum(x, "dp")
    if rank == 0:
        log(y)
    return y
"""

R7_CLEAN_BOTH_ARMS = """
from jax import lax

def step(x, rank):
    if rank == 0:
        return lax.psum(x, "dp")
    else:
        return lax.pmax(x, "dp")
"""


def test_r7_fires_on_rank_guarded_collective():
    assert _ids(R7_BAD, "mxnet_tpu/parallel/fx.py") == ["R7"]


def test_r7_fires_on_process_index_guarded_rendezvous():
    assert _ids(R7_BAD_PROCESS_INDEX, "mxnet_tpu/kvstore/fx.py") == ["R7"]


def test_r7_clean_when_collective_hoisted_or_symmetric():
    assert _ids(R7_CLEAN_HOIST, "mxnet_tpu/parallel/fx.py") == []
    # both arms rendezvous: divergent SHAPE maybe, but not the
    # one-arm-launches class R7 hunts
    assert _ids(R7_CLEAN_BOTH_ARMS, "mxnet_tpu/parallel/fx.py") == []


def test_r7_scoped_to_spmd_modules():
    assert _ids(R7_BAD, "mxnet_tpu/image/fx.py") == []


# ----------------------------------------------------------------------
# R8 — comm/board namespace discipline
# ----------------------------------------------------------------------
R8_BAD_NAKED = """
def build(root, rank, world):
    votes = FileComm(root, rank, world)
    beats = FileComm(root, rank, world)
    return votes, beats
"""

R8_BAD_DUP = """
def build(root, rank, world):
    votes = FileComm(root, rank, world, namespace="x")
    beats = FileComm(root, rank, world, namespace="x")
    return votes, beats
"""

R8_BAD_SERVICE = """
def build():
    return CoordServiceComm(), CoordServiceComm()
"""

R8_BAD_BOARDS = """
def build(root):
    return FileBoard(root), FileBoard(root)
"""

R8_CLEAN = """
def build(root, rank, world, epoch):
    votes = FileComm(root, rank, world, namespace="votes")
    beats = FileComm(root, rank, world, namespace="hb%d" % epoch)
    other = FileComm(root + "/other", rank, world)
    return votes, beats, other
"""


def test_r8_fires_on_second_naked_comm_per_root():
    assert _ids(R8_BAD_NAKED, "mxnet_tpu/parallel/fx.py") == ["R8"]
    assert _ids(R8_BAD_SERVICE, "mxnet_tpu/parallel/fx.py") == ["R8"]
    assert _ids(R8_BAD_BOARDS, "tools/fx.py") == ["R8"]


def test_r8_fires_on_duplicate_literal_namespace():
    assert _ids(R8_BAD_DUP, "mxnet_tpu/parallel/fx.py") == ["R8"]


def test_r8_clean_with_distinct_namespaces_or_roots():
    assert _ids(R8_CLEAN, "mxnet_tpu/parallel/fx.py") == []


# ----------------------------------------------------------------------
# level 2 — HLO named checks
# ----------------------------------------------------------------------
_CONV = ('    %%2 = stablehlo.convolution(%%0, %%1) dim_numbers = '
         '[%s]x[o, 0, 1, i]->[%s], window = {stride = [2, 2]} : '
         '(tensor<8x224x224x3xbf16>, tensor<64x7x7x3xbf16>) -> '
         'tensor<8x112x112x64xbf16>\n')


def test_hlo_transpose_free():
    bad = "  %1 = stablehlo.transpose %0 -> tensor<8x3x224x224xf32>\n"
    assert not hlo.check_transpose_free(bad).ok
    clean = "  %1 = stablehlo.transpose %0 -> tensor<64x128xf32>\n"
    assert hlo.check_transpose_free(clean).ok


def test_hlo_convs_channel_minor():
    good = _CONV % ("b, 0, 1, f", "b, 0, 1, f")
    wgrad = _CONV % ("f, 0, 1, b", "f, 0, 1, b")
    assert hlo.check_convs_channel_minor(good + wgrad).ok
    nchw = _CONV % ("b, f, 0, 1", "b, f, 0, 1")
    res = hlo.check_convs_channel_minor(nchw)
    assert not res.ok and "spatial-minor" in res.details[0]


def test_hlo_no_host_transfers():
    for bad in ('  %1 = "stablehlo.send"(%0) : ...\n',
                '  outfeed(f32[8] %x)\n',
                '  custom-call(%x), custom_call_target="MoveToHost"\n'):
        res = hlo.check_no_host_transfers(bad)
        assert not res.ok, bad
    assert hlo.check_no_host_transfers(
        "  %1 = stablehlo.add %0, %0\n").ok


def test_hlo_no_full_param_all_gather():
    bad = ('  %3 = "stablehlo.all_gather"(%2) : '
           '(tensor<16x64xf32>) -> tensor<128x64xf32>\n')
    res = hlo.check_no_full_param_all_gather(bad,
                                             param_shapes=[(128, 64)])
    assert not res.ok and "full parameter" in res.details[0]
    # compiled-HLO spelling: result shape BEFORE the op name
    compiled = ('  %ag = f32[128,64]{1,0} all-gather('
                'f32[16,64]{1,0} %p), dimensions={0}\n')
    assert hlo.all_gather_results(compiled) == [(128, 64)]
    assert not hlo.check_no_full_param_all_gather(
        compiled, param_shapes=[(128, 64)]).ok
    # a shard-sized gather under ZeRO-1 is the expected pattern
    ok = ('  %3 = "stablehlo.all_gather"(%2) : '
          '(tensor<2x64xf32>) -> tensor<16x64xf32>\n')
    assert hlo.check_no_full_param_all_gather(
        ok, param_shapes=[(128, 64)]).ok
    # without shapes the screen cannot prove anything: ok, but it must
    # say so instead of going vacuously green
    res = hlo.check_no_full_param_all_gather(bad)
    assert res.ok and "screen skipped" in res.details[0]


def test_hlo_collective_permute_overlap():
    sync = "  %2 = collective-permute(%1), channel_id=1\n"
    res = hlo.check_collective_permute_overlap(sync)
    assert not res.ok and "synchronous" in res.details[0]
    asynch = ("  %2 = collective-permute-start(%1)\n"
              "  %3 = fusion(%2)\n"
              "  %4 = collective-permute-done(%2)\n")
    assert hlo.check_collective_permute_overlap(asynch).ok
    assert not hlo.check_collective_permute_overlap(
        "  %1 = add(%0)\n", require_present=True).ok


def test_hlo_collective_present():
    stable = "  %2 = stablehlo.collective_permute %1, ...\n"
    compiled = "  %2 = collective-permute-start(%1)\n"
    for txt in (stable, compiled):
        assert hlo.check_collective_present(
            txt, kinds=("collective_permute",)).ok, txt
    res = hlo.check_collective_present("  %1 = add(%0)\n",
                                       kinds=("collective_permute",))
    assert not res.ok and "missing" in res.details[0]
    # asking for an unknown kind is an error finding, not a silent pass
    res = hlo.check_collective_present(stable, kinds=("warp_shuffle",))
    assert not res.ok and "unknown collective kind" in res.details[0]
    assert hlo.collective_counts(stable)["collective_permute"] == 1


def test_hlo_collective_overlap_generalized():
    """check_collective_overlap: any kind, async-only enforcement, the
    TPU ``async-collective-start`` fusion-wrapper spelling, and the
    allow_sync relaxation for partially-async artifacts."""
    sync = "  %2 = f32[8] all-gather(f32[1] %1), dimensions={0}\n"
    res = hlo.check_collective_overlap(sync, kinds=("all_gather",))
    assert not res.ok and "synchronous" in res.details[0]
    asynch = ("  %2 = all-gather-start(%1)\n"
              "  %3 = fusion(%2)\n"
              "  %4 = all-gather-done(%2)\n")
    assert hlo.check_collective_overlap(asynch, kinds=("all_gather",),
                                        require_present=True).ok
    # TPU wrapper form: the sync-spelled op lives INSIDE the
    # async_collective_fusion computation and must not count as sync
    wrapper = (
        "%async_collective_fusion.1 (p0: f32[1]) -> (f32[8]) {\n"
        "  %ag = f32[8] all-gather(f32[1] %p0), dimensions={0}\n"
        "}\n"
        "ENTRY %main {\n"
        '  %async-collective-start = (f32[8]) fusion(%x), '
        'calls=%async_collective_fusion.1, frontend_attributes='
        '{async_collective_name="all-gather-start.1"}\n'
        "  %f = f32[8] fusion(%y)\n"
        "  %async-collective-done = f32[8] fusion(%gte)\n"
        "}\n")
    assert hlo.check_collective_overlap(wrapper, kinds=("all_gather",),
                                        require_present=True).ok
    # partially-async artifact: sync ops fail strict, pass allow_sync
    mixed = asynch + sync
    assert not hlo.check_collective_overlap(mixed,
                                            kinds=("all_gather",)).ok
    assert hlo.check_collective_overlap(mixed, kinds=("all_gather",),
                                        require_present=True,
                                        allow_sync=True).ok
    # absence with require_present is a finding, not a vacuous pass
    res = hlo.check_collective_overlap("  %1 = add(%0)\n",
                                       kinds=("all_gather",),
                                       require_present=True)
    assert not res.ok and "missing" in res.details[0]


def test_hlo_overlap_window():
    """check_overlap_window: the compiled module is scheduled, so a
    done op immediately after its start is a serial hop; compute
    between them is the overlap window."""
    overlapped = ("  %s0 = collective-permute-start(%1)\n"
                  "  %c = f32[8] fusion(%2), kind=kLoop\n"
                  "  %d0 = collective-permute-done(%s0)\n")
    assert hlo.check_overlap_window(overlapped).ok
    serial = ("  %s0 = collective-permute-start(%1)\n"
              "  %d0 = collective-permute-done(%s0)\n")
    res = hlo.check_overlap_window(serial)
    assert not res.ok and "immediately after" in res.details[0]
    res = hlo.check_overlap_window("  %1 = add(%0)\n")
    assert not res.ok and "no async" in res.details[0]
    # copy-start/slice-start are memory ops, not collectives
    assert not hlo.check_overlap_window(
        "  %s = copy-start(%1)\n  %d = copy-done(%s)\n").ok


def test_hlo_remat_recompute():
    base = _CONV % ("b, 0, 1, f", "b, 0, 1, f")
    remat = base + base + "  optimization_barrier\n"
    assert hlo.check_remat_recompute(base, remat, min_extra_convs=1).ok
    res = hlo.check_remat_recompute(base, base + base,
                                    min_extra_convs=1)
    assert not res.ok and "optimization_barrier" in res.details[0]


# ----------------------------------------------------------------------
# engine: baseline semantics + self-scan
# ----------------------------------------------------------------------
def test_baseline_loader_rejects_malformed_lines(tmp_path):
    p = tmp_path / "b.txt"
    p.write_text("R2 tools/x.py 1\n")  # no justification
    with pytest.raises(ValueError):
        lint.load_baseline(str(p))
    p.write_text("# comment\n\nR2 tools/x.py 2 -- known journal\n")
    assert lint.load_baseline(str(p)) == {
        ("R2", "tools/x.py"): (2, "known journal")}


def test_apply_baseline_counts_and_ratchet():
    diags = [lint.Diagnostic("R2", "tools/x.py", i, "m")
             for i in (1, 2, 3)]
    baseline = {("R2", "tools/x.py"): (2, "why"),
                ("R4", "gone.py"): (1, "stale")}
    un, kept, stale = lint.apply_baseline(diags, baseline)
    assert [d.line for d in un] == [3]
    assert len(kept) == 2
    assert stale == [(("R4", "gone.py"), 1, 0)]


def test_self_scan_repo_clean_modulo_baseline():
    """THE gate: the repo's own source carries zero unbaselined
    diagnostics, and no baseline entry is stale — the lint ratchets."""
    diags = lint.lint_paths(ROOT)
    baseline = lint.load_baseline(
        os.path.join(ROOT, "tools", "mxlint_baseline.txt"))
    un, _, stale = lint.apply_baseline(diags, baseline)
    assert not un, "unbaselined diagnostics:\n%s" % "\n".join(
        d.format() for d in un)
    assert not stale, ("stale baseline entries — the code improved, "
                       "ratchet the baseline down: %s" % stale)


def test_every_rule_is_live():
    """No rule may be vacuous: each R1–R8 has a firing fixture above,
    and the registry carries exactly the documented rules."""
    assert set(lint.RULES) == {"R1", "R2", "R3", "R4", "R5", "R6",
                               "R7", "R8"}
    for r in lint.RULES.values():
        assert r.invariant and r.scope


@pytest.mark.integration
def test_mxlint_cli_standalone(tmp_path):
    """tools/mxlint.py runs without importing mxnet_tpu (no jax init):
    exit 0 on the clean repo, 1 on a failing --hlo artifact."""
    cli = os.path.join(ROOT, "tools", "mxlint.py")
    r = subprocess.run([sys.executable, cli], cwd=ROOT,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    bad = tmp_path / "bad.mlir"
    bad.write_text('  %1 = "stablehlo.send"(%0)\n')
    r = subprocess.run([sys.executable, cli, "--hlo", str(bad),
                        "--hlo-check", "no_host_transfers"],
                       cwd=ROOT, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode == 1 and "no_host_transfers FAIL" in r.stdout
    # a typo'd rule id must error, not silently run zero rules
    r = subprocess.run([sys.executable, cli, "--rules", "R9"],
                       cwd=ROOT, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode == 2 and "unknown rule" in r.stderr
    # a rule subset must not misreport other rules' baseline as stale
    r = subprocess.run([sys.executable, cli, "--rules", "R2"],
                       cwd=ROOT, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode == 0 and "stale baseline entry" not in r.stderr
    # comma syntax tolerates spaces, same as --hlo-check
    r = subprocess.run([sys.executable, cli, "--rules", "R7, R8"],
                       cwd=ROOT, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    # a typo'd --hlo-check errors instead of KeyError-ing mid-scan
    r = subprocess.run([sys.executable, cli, "--hlo", os.devnull,
                        "--hlo-check", "no_such_check"],
                       cwd=ROOT, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode == 2 and "unknown --hlo-check" in r.stderr


@pytest.mark.integration
def test_mxlint_cli_stale_baseline_and_github_format(tmp_path, monkeypatch,
                                                     capsys):
    """A stale baseline entry fails the gate and is printed entry-by-
    entry (with its justification); --format github emits workflow
    commands for diagnostics."""
    cli = os.path.join(ROOT, "tools", "mxlint.py")
    stale = tmp_path / "stale.txt"
    stale.write_text("R2 tools/gone.py 3 -- torn writer long since "
                     "fixed\n")
    r = subprocess.run([sys.executable, cli, "--baseline", str(stale),
                        "mxnet_tpu/analysis"],
                       cwd=ROOT, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode == 1
    assert "stale baseline entry 'R2 tools/gone.py 3" in r.stderr
    assert "torn writer long since fixed" in r.stderr
    # github format: diagnostics become ::error workflow commands (the
    # repo's own baseline is empty, so the fixture is a virtual tree
    # holding one R5 violation, scanned by the tool's own main)
    import importlib.util
    spec = importlib.util.spec_from_file_location("mxlint_cli", cli)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    fx = tmp_path / "mxnet_tpu" / "parallel"
    fx.mkdir(parents=True)
    (fx / "fx.py").write_text(R5_BAD_STORE)
    monkeypatch.setattr(tool, "ROOT", str(tmp_path))
    rc = tool.main(["--format", "github", "--no-baseline", "--rules", "R5",
                    "mxnet_tpu/parallel"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "::error file=mxnet_tpu/parallel/fx.py" in out
    assert "title=mxlint R5" in out


@pytest.mark.integration
def test_mxlint_cli_hlo_baseline_ratchet(tmp_path):
    """--hlo-baseline turns --hlo into the chip-independent perf
    ratchet: exit 0 when counts+verdicts match the checked-in baseline,
    1 on a collective REGRESSION (count up), 1 on a stale entry (count
    down or a check newly passing — the improvement must be locked in
    via hlo_snapshot.py --write-baseline), and 1 on a missing entry."""
    import json as _json
    cli = os.path.join(ROOT, "tools", "mxlint.py")
    art = tmp_path / "prog_a.hlo.txt"
    art.write_text("  %2 = collective-permute-start(%1)\n"
                   "  %c = f32[8] fusion(%2)\n"
                   "  %3 = collective-permute-done(%2)\n")
    base = tmp_path / "base.json"

    def run(entry):
        base.write_text(_json.dumps({"prog_a": entry} if entry else {}))
        return subprocess.run(
            [sys.executable, cli, "--hlo", str(art),
             "--hlo-baseline", str(base)],
            cwd=ROOT, capture_output=True, text=True, timeout=120)

    from mxnet_tpu.analysis import hlo as _hlo
    txt = art.read_text()
    good = {"collective_counts": _hlo.collective_counts(txt),
            "checks": {r.name: r.ok
                       for r in _hlo.run_text_checks(txt)}}
    r = run(good)
    assert r.returncode == 0 and "baseline MATCH" in r.stdout, \
        r.stdout + r.stderr
    # count regression (baseline allows fewer collectives than found)
    worse = dict(good, collective_counts=dict(
        good["collective_counts"], collective_permute=0))
    r = run(worse)
    assert r.returncode == 1 and "REGRESSION" in r.stdout
    # stale: baseline expects MORE collectives than the program has now
    stale = dict(good, collective_counts=dict(
        good["collective_counts"], collective_permute=5))
    r = run(stale)
    assert r.returncode == 1 and "stale baseline" in r.stdout
    # check verdict regression: baseline says the overlap check passes,
    # artifact now fails it
    sync_art = tmp_path / "prog_a.hlo.txt"
    sync_art.write_text("  %2 = collective-permute(%1)\n")
    flipped = {"collective_counts":
               _hlo.collective_counts(sync_art.read_text()),
               "checks": dict(good["checks"])}
    r = run(flipped)
    assert r.returncode == 1 and "regressed ok -> FAIL" in r.stdout
    # unknown program name
    r = run(None)
    assert r.returncode == 1 and "no hlo baseline entry" in r.stderr
