"""The four-chip cell ``t40x4.mine`` driven end to end on four CPU devices.

One subprocess with four host devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``) makes a tiny copy
of the benchmark (``tinybench.make_tiny``), cuts ``quest-t40i10d400k`` to
12,000 rows, four splits of ``t40.mine``'s tiny 3,000, and runs the cell
three times: sound, traced, and with the exchange left out (the ``psum``
over ``data`` replaced by each chip's own counts).
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from tinybench import REPO

HERE = Path(__file__).resolve().parent

SCRIPT = textwrap.dedent("""
    import json, sys
    from pathlib import Path
    sys.path.insert(0, sys.argv[2])
    from tinybench import make_tiny, run_tiny
    root = make_tiny(Path(sys.argv[1]))
    p = root / "chipbench/configs/quest-t40i10d400k.json"
    c = json.loads(p.read_text())
    c["data"].update(n_txns=12000, n_items=100, avg_width=8, n_patterns=20,
                     avg_pattern_len=4)
    c["mining"].update(min_sup=0.05, impl="vertical")
    p.write_text(json.dumps(c))
    out = {"sound": run_tiny(root, "t40x4.mine"),
           "traced": run_tiny(root, "t40x4.mine", trace=True)}
    import jax
    psum = jax.lax.psum
    jax.lax.psum = lambda x, axis_name, **kw: (
        x if axis_name == "data" else psum(x, axis_name, **kw))
    out["no_exchange"] = run_tiny(root, "t40x4.mine")
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("mesh4")
    env = dict(os.environ, JAX_PLATFORMS="cpu", REPRO_AUTOTUNE="0",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               REPRO_AUTOTUNE_CACHE=str(tmp / "autotune.json"),
               REPRO_COSTMODEL_CACHE=str(tmp / "cm.json"),
               TPU_LOG_DIR=str(tmp / "tpu_logs"),
               PYTHONPATH=os.pathsep.join([str(REPO), str(REPO / "src")]))
    p = subprocess.run([sys.executable, "-c", SCRIPT, str(tmp / "bench"),
                        str(HERE)], env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stdout + "\n" + p.stderr
    (line,) = [x for x in p.stdout.splitlines() if x.startswith("RESULT ")]
    return json.loads(line[len("RESULT "):])


def test_sound_four_chip_run_is_correct(runs):
    out = runs["sound"]
    assert out["device"]["count"] == 4
    assert out["correct"] is True
    assert set(out["metrics"]) == {"setup_s", "mine_s"}
    assert out["metrics"]["mine_s"]["value"] > 0
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["check"]["itemset_mismatches"]["value"] == 0


def test_exchange_left_out_is_not_correct(runs):
    out = runs["no_exchange"]
    assert out["correct"] is False
    assert out["check"]["itemset_mismatches"]["value"] > 0
    assert out["failed"] == out["attempted"] >= 1


def test_exchange_metrics_read_nothing_on_the_cpu(runs):
    out = runs["traced"]
    assert out["correct"] is True
    got = out["metrics"]
    # no device plane on the CPU: the trace's readers stay silent, and the
    # run does not fail
    assert not got.keys() & {"collective_share.mine4",
                             "exchange_roofline.mine4", "idle_share.mine",
                             "count_roofline.mine"}
    assert got["scatter_pack_ms.mine"]["value"] > 0
    assert got["scatter_pack_ms.mine"]["value"] <= \
        got["scatter_ms.mine"]["value"]
    assert got["h2d_bytes.mine"]["value"] > 0
