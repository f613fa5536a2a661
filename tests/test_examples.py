"""Every example script runs end to end.

The reference CI executes its example directory the same way
(``tests/tutorials``, ``example/`` smoke runs in the nightlies): an
example that no longer runs is a broken front door.  Each script is
executed in its own interpreter via a wrapper that pins the CPU backend
before any backend init and provides the 8-device virtual mesh the
multi-chip examples expect.

``train_resnet_spmd.py`` is exercised indirectly instead (its TrainStep-
on-mesh path is tests/test_parallel.py and its model is the benchmark's
ResNet cell): a batch-256 ResNet-50 compile is minutes of XLA CPU time
the suite cannot afford per run.
"""
import os
import subprocess
import sys

import pytest

EXAMPLES = os.path.join(os.path.dirname(__file__), "..", "examples")

_RUNNER = (
    "import sys, os;"
    "os.environ['XLA_FLAGS'] = os.environ.get('XLA_FLAGS', '') + "
    "' --xla_force_host_platform_device_count=8';"
    "import jax; jax.config.update('jax_platforms', 'cpu');"
    "path = sys.argv[1];"
    "sys.argv = [path];"      # argparse-using examples see a clean argv
    "g = {'__name__': '__main__', '__file__': path};"
    "exec(open(path).read(), g)"
)

CASES = [
    # (script, timeout_s, expected output fragments, extra env)
    ("mnist_lenet.py", 900, ["final accuracy:"], {}),
    ("train_llm_tp.py", 900, ["mesh:", "params:"], {}),
    ("train_moe_lm.py", 900, ["loss"], {}),
    ("long_context_ring_attention.py", 900,
     ["ring attention out:", "max error"], {}),
    # same script through the hierarchical 2-level (2 slices x 4) ring
    # (small seq: the 2-level path is the point, the full 8k cost is
    # already paid by the flat case above)
    ("long_context_ring_attention.py", 900,
     ["ring attention out:", "max error"],
     {"RING_EXAMPLE_SLICES": "2", "RING_EXAMPLE_SEQ": "2048"}),
    ("import_third_party_onnx.py", 600, [], {}),
    ("int8_deploy_onnx.py", 600, [], {}),
    ("ssd_detection.py", 900, [], {"EXAMPLE_EPOCHS": "1"}),
    ("train_resume_sharded.py", 900,
     ["resume is trajectory-exact across topologies"], {}),
]


@pytest.mark.parametrize("script,timeout,expect,extra_env",
                         CASES, ids=[c[0] for c in CASES])
def test_example_runs(script, timeout, expect, extra_env):
    path = os.path.join(EXAMPLES, script)
    env = {**os.environ, **extra_env}
    p = subprocess.run([sys.executable, "-c", _RUNNER, path],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert p.returncode == 0, "%s failed:\n%s" % (script, p.stderr[-3000:])
    for frag in expect:
        assert frag in p.stdout, "%s output missing %r:\n%s" % (
            script, frag, p.stdout[-2000:])
