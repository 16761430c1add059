"""Bring-up contracts (ISSUE 21), all on the CPU: the one kernel-mode
decision, compile-cache placement, chip census and per-worker chip
binding (fake chip ids), launch scripts that fail without a chip, and
``chip_smoke.py`` — its phase bodies at ``nano`` in-process, and the
command itself failing, naming the platform, where jax finds no
accelerator."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


# ------------------------------------------------------- device decisions
def test_pallas_mode_is_decided_by_platform(monkeypatch):
    import jax

    from ray_tpu._private import chip

    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert chip.pallas_interpret() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert chip.pallas_interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        chip.pallas_interpret()


def test_attention_plan_chooses_by_shape_not_platform():
    """``auto`` resolves on shape alone; the mode is the helper's."""
    import dataclasses

    from ray_tpu.models import gpt

    small = dataclasses.replace(gpt.CONFIGS["small"], attn_backend="auto")
    assert gpt.attention_plan(small, 1024) == {
        "backend": "flash", "mode": "interpret"}    # this is a CPU
    assert gpt.attention_plan(small, 100) == {"backend": "xla",
                                              "mode": "xla"}


def test_peak_table_is_exact_or_an_error():
    from ray_tpu._private import chip

    assert chip.peak_flops("TPU v5 lite") == 197e12
    for kind in ("cpu", "TPU v5", "tpu v5 lite"):
        with pytest.raises(RuntimeError, match="no peak"):
            chip.peak_flops(kind)
    with pytest.raises(RuntimeError, match="platform='cpu'"):
        chip.require_tpu()


def test_compile_cache_placement(tmp_path):
    """Variable set → untouched; unset → ONE fixed path inside the
    checkout, the same from any process and any working directory."""
    code = ("import os, sys; sys.path.insert(0, %r); "
            "from ray_tpu._private.chip import ensure_compile_cache; "
            "print(ensure_compile_cache()); "
            "print(os.environ['JAX_COMPILATION_CACHE_DIR'])" % ROOT)

    def run(env_value, cwd):
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        if env_value is not None:
            env["JAX_COMPILATION_CACHE_DIR"] = env_value
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             cwd=cwd, capture_output=True, text=True,
                             check=True).stdout.split()
        assert out[0] == out[1]
        return out[0]

    assert run("/some/dir", ROOT) == "/some/dir"
    fixed = os.path.join(ROOT, ".jax_cache")
    assert run(None, ROOT) == fixed
    assert run(None, str(tmp_path)) == fixed
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split(), "must be git-ignored"


def test_chip_census_counts_numbered_nodes_only(monkeypatch):
    from ray_tpu._private import accelerators as acc

    monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
    nodes = {"/dev/accel*": [],
             "/dev/vfio/*": ["/dev/vfio/3", "/dev/vfio/vfio"]}
    monkeypatch.setattr(acc.glob, "glob", lambda pat: nodes[pat])
    # One chip, and libtpu calls it chip 0 whatever the node's number.
    assert acc.local_chip_ids() == ["0"] and acc.local_chip_count() == 1
    nodes["/dev/accel*"] = [f"/dev/accel{i}" for i in range(4)]
    assert acc.local_chip_ids() == ["0", "1", "2", "3"]
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "2,3")
    assert acc.local_chip_ids() == ["2", "3"]
    # Hand-given counts beyond the census get distinct fake ids.
    assert acc.node_chip_ids(3.0) == ["0", "1", "2"]


def test_chip_visibility_env():
    from ray_tpu._private.accelerators import chip_visibility_env as env

    assert env([], 4) == {"TPU_VISIBLE_CHIPS": "", "JAX_PLATFORMS": "cpu"}
    assert env(["0", "1", "2", "3"], 4) == {}      # the whole node
    one = env(["2"], 4)
    assert one["TPU_VISIBLE_CHIPS"] == "2"
    assert one["TPU_CHIPS_PER_HOST_BOUNDS"] == "1,1,1"
    assert env(["0", "1"], 4)["TPU_CHIPS_PER_HOST_BOUNDS"] == "1,2,1"
    with pytest.raises(ValueError):
        env(["0", "1", "2"], 4)


def test_native_codec_is_keyed_on_source_content():
    import hashlib

    from ray_tpu import _native

    src = os.path.join(os.path.dirname(_native.__file__), "codec.cpp")
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    assert f".{digest}.so" in os.path.basename(_native._build())


# ------------------------------------------- one process for each chip
@pytest.fixture(scope="module")
def fake_chip_cluster():
    """One cluster for both tests below (a shutdown costs ~9 s)."""
    import ray_tpu as rt

    if rt.is_initialized():
        rt.shutdown()
    rt.init(num_cpus=8, num_tpus=4)      # no such chips: ids are fake
    yield rt
    rt.shutdown()


def test_tpu_grant_binds_chips(fake_chip_cluster):
    """Two ``num_tpus=1`` actors get disjoint chips, a ``num_tpus=0``
    actor gets none (and is pinned to the CPU), the binding is in the
    worker's environment before user code runs, and a pooled worker is
    reused only for the same set."""
    rt = fake_chip_cluster

    @rt.remote
    class Holder:
        def env(self):
            return (os.environ.get("TPU_VISIBLE_CHIPS"),
                    os.environ.get("JAX_PLATFORMS"), os.getpid(),
                    "jax" in sys.modules)

    a, b = (Holder.options(num_tpus=1).remote() for _ in range(2))
    none = Holder.options(num_tpus=0).remote()
    ea, eb, en = rt.get([x.env.remote() for x in (a, b, none)],
                        timeout=60)
    assert {ea[0], eb[0]} == {"0", "1"}
    assert en[:2] == ("", "cpu")
    assert not (ea[3] or eb[3] or en[3]), "bound before jax was imported"

    @rt.remote(num_tpus=1)
    def chip_task():
        return os.environ.get("TPU_VISIBLE_CHIPS"), os.getpid()

    first = rt.get(chip_task.remote(), timeout=60)
    again = rt.get(chip_task.remote(), timeout=60)
    assert first[0] == "2"                      # the next free chip
    assert again == first                       # same set, same process
    # Two chips come as mesh neighbours — (2,3), never (0,2) — and the
    # pooled worker that held chip 2 alone is not reused for the pair.
    rt.kill(a if ea[0] == "0" else b)
    pair = rt.get(Holder.options(num_tpus=2).remote().env.remote(),
                  timeout=60)
    assert pair[0] == "2,3" and pair[2] != first[1]
    with pytest.raises(ValueError, match="whole number"):
        Holder.options(num_tpus=0.5).remote()


def test_replica_start_error_reaches_serve_run(fake_chip_cluster):
    """No ready deadline: ``serve.run`` returns when the replica has
    constructed, or raises the replica's OWN error (here: the message a
    compiler would have left in a worker log)."""
    from ray_tpu import serve

    @serve.deployment
    class Broken:
        def __init__(self):
            raise ValueError("Mosaic failed to compile TPU kernel: xyz")

        def __call__(self, _):
            return 0

    try:
        with pytest.raises(Exception, match="Mosaic failed to compile"):
            serve.run(Broken.bind(), name="broken", route_prefix=None,
                      _proxy=False)
    finally:
        serve.shutdown()


# ------------------------------------------------------ launch scripts
@pytest.mark.parametrize("script", ["chip_smoke.py", "bench.py",
                                    "benchmarks/lm_sharded.py"])
def test_chip_scripts_fail_without_a_chip(script):
    """On a CPU each exits non-zero, names the platform it found, and
    prints no metric and no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    r = subprocess.run([sys.executable, os.path.join(ROOT, script)],
                       env=env, cwd=ROOT, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0
    assert "platform='cpu'" in r.stdout + r.stderr
    assert '"metric"' not in r.stdout and '"ok"' not in r.stdout


def test_chip_smoke_alone_fails(tmp_path):
    """In a directory that holds ``chip_smoke.py`` and nothing else of
    the repo it cannot start, and says nothing that looks like a
    result."""
    import shutil

    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "chip_smoke.py"], env=env,
                       cwd=tmp_path, capture_output=True, text=True,
                       timeout=60)
    assert r.returncode != 0 and '"ok"' not in r.stdout


# ------------------------------------------- the smoke's phases at nano
def test_smoke_serve_phase_nano():
    import ray_tpu as rt

    import chip_smoke

    if rt.is_initialized():
        rt.shutdown()
    out = chip_smoke.phase_serve("nano", require_tpu=False)
    (rep,) = out["replicas"]
    assert rep["warm_up"]["attn_kernel_mode"] == "interpret"
    assert rep["stats"]["prefills"] == len(out["tokens"]) == 7
    assert out["repeat_identical"] and rep["native_codec"]


def test_smoke_kernel_phase_nano():
    import chip_smoke

    out = chip_smoke.phase_kernels("nano", require_tpu=False)
    assert set(out["paged"]) == {"fp", "int8"} and "nano" in out["flash"]
    assert not out["paged"]["fp"]["mosaic"]         # interpreted here


def test_smoke_train_phase_nano():
    import chip_smoke

    out = chip_smoke.phase_train("nano", require_tpu=False)
    assert out["losses"][-1] < out["losses"][0]
    assert out["attention"] == {"backend": "xla", "mode": "xla"}
