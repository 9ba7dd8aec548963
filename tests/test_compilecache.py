"""Compile-lifecycle subsystem tests: bucket enumeration/ordering, warmup
state + /readyz warm-fraction gating, persistent compilation-cache reuse
across processes, and the prewarmed model-generation swap (no request-path
compile after the flip).

The dynamic compile assertions ride the same ``jax.monitoring`` counter the
serving bench asserts on (``compilecache.compiles_total``): an in-memory
jit-dispatch cache hit fires nothing, so "zero delta" means literally no
XLA compile happened.
"""

import asyncio
import json
import os
import subprocess
import sys
import threading
import time

import httpx
import numpy as np
import pytest
from aiohttp import web

from oryx_tpu.common import compilecache
from oryx_tpu.common import config as cfg
from oryx_tpu.common import ioutils
from oryx_tpu.serving.app import ServingLayer, make_app
from oryx_tpu.serving.batcher import floor_pow2, pow2_buckets
from oryx_tpu.transport import topic as tp


# ---------------------------------------------------------------------------
# bucket enumeration + warmup ordering
# ---------------------------------------------------------------------------


def test_pow2_buckets_enumeration():
    assert pow2_buckets(256) == [1, 2, 4, 8, 16, 32, 64, 128, 256]
    assert pow2_buckets(1) == [1]
    assert pow2_buckets(3) == [1, 2]  # non-pow2 cap floors, like the coalescer
    for cap in (1, 2, 3, 7, 64, 100, 256, 1000):
        buckets = pow2_buckets(cap)
        assert buckets == sorted(buckets)  # smallest first: incremental ready
        assert buckets[-1] == floor_pow2(cap)
        # every size the coalescer can pad a real flush to is warmed
        for n_real in range(1, floor_pow2(cap) + 1):
            n_pad = 1 << max(0, n_real - 1).bit_length()
            assert n_pad in buckets


def test_warmup_state_lifecycle_and_readiness():
    st = compilecache.WarmupState()
    # unarmed: warmup not configured -> never gates
    assert st.ready(1.0) and st.warm_fraction() == 1.0
    st.arm()
    # armed but no ladder yet: the model-loaded->warmer-pickup window must
    # not flap ready
    assert not st.ready(1.0)
    st.begin(4)
    assert st.snapshot() == {"done": 0, "total": 4}
    st.bucket_done()
    assert st.warm_fraction() == 0.25
    assert st.ready(0.25) and not st.ready(0.5)
    for _ in range(3):
        st.bucket_done()
    st.finish()
    assert st.ready(1.0)
    # completion is sticky: a staged generation re-warming off-path must not
    # drop the replica out of rotation
    st.begin(4)
    assert st.ready(1.0)
    st.reset()
    assert st.ready(1.0)  # back to unarmed


def test_warmup_state_mark_trivial():
    st = compilecache.WarmupState()
    st.arm()
    assert not st.ready(1.0)
    st.mark_trivial()  # app family with no batched top-N
    assert st.ready(1.0)


# ---------------------------------------------------------------------------
# /readyz warm-fraction gating
# ---------------------------------------------------------------------------


class _Model:
    def get_fraction_loaded(self):
        return 1.0


class _Manager:
    def get_model(self):
        return _Model()

    def is_read_only(self):
        return True


class _AppServer:
    def __init__(self, app):
        self.port = ioutils.choose_free_port()
        self._loop = asyncio.new_event_loop()
        self._started = threading.Event()
        self._app = app
        self._thread = threading.Thread(target=self._serve, daemon=True)

    def _serve(self):
        asyncio.set_event_loop(self._loop)
        runner = web.AppRunner(self._app, access_log=None)
        self._loop.run_until_complete(runner.setup())
        site = web.TCPSite(runner, "127.0.0.1", self.port)
        self._loop.run_until_complete(site.start())
        self._started.set()
        self._loop.run_forever()
        self._loop.run_until_complete(runner.cleanup())

    def __enter__(self) -> str:
        self._thread.start()
        assert self._started.wait(15), "app server failed to start"
        return f"http://127.0.0.1:{self.port}"

    def __exit__(self, *exc):
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(timeout=10)


@pytest.fixture
def _clean_warmup_state():
    compilecache.warmup_state().reset()
    yield compilecache.warmup_state()
    compilecache.warmup_state().reset()


def test_readyz_warm_fraction_gates_cold_replica(_clean_warmup_state):
    st = _clean_warmup_state
    app = make_app(cfg.get_default(), _Manager())
    with _AppServer(app) as base:
        with httpx.Client(base_url=base, timeout=30) as client:
            # warmup not configured: ready as before
            r = client.get("/readyz")
            assert r.status_code == 200
            assert r.json()["warmup"] == {"done": 0, "total": 0}
            # armed cold replica: model loaded but buckets not compiled
            st.arm()
            r = client.get("/readyz")
            assert r.status_code == 503
            assert r.json()["warmup_status"] == "cold"
            # partial ladder below the default 1.0 fraction: still cold
            st.begin(4)
            st.bucket_done()
            r = client.get("/readyz")
            assert r.status_code == 503
            assert r.json()["warmup"] == {"done": 1, "total": 4}
            # ladder completes -> ready, and sticky through a new cycle
            for _ in range(3):
                st.bucket_done()
            st.finish()
            assert client.get("/readyz").status_code == 200
            st.begin(4)
            assert client.get("/readyz").status_code == 200


def test_readyz_configurable_warm_fraction(_clean_warmup_state):
    st = _clean_warmup_state
    app = make_app(
        cfg.overlay_on({"oryx.compile.ready-warm-fraction": 0.5},
                       cfg.get_default()),
        _Manager(),
    )
    with _AppServer(app) as base:
        with httpx.Client(base_url=base, timeout=30) as client:
            st.arm()
            st.begin(4)
            st.bucket_done()
            assert client.get("/readyz").status_code == 503  # 1/4 < 0.5
            st.bucket_done()
            assert client.get("/readyz").status_code == 200  # 2/4 >= 0.5


# ---------------------------------------------------------------------------
# persistent compilation cache
# ---------------------------------------------------------------------------

_CACHE_PROBE = """
import json, os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import time
from oryx_tpu.common import compilecache
from oryx_tpu.common import config as cfg

# argv[2] = how the directory is placed: "key" sets oryx.compile.cache-dir,
# "env"/"default" leave the config alone (the environment, or nothing, does)
overlay = {"oryx.compile.cache-dir": sys.argv[1]} if sys.argv[2] == "key" else {}
if sys.argv[2] == "default":
    compilecache.DEFAULT_CACHE_DIR = sys.argv[1]  # a scratch "checkout"
config = cfg.overlay_on(overlay, cfg.get_default())
compilecache.configure(config)

import jax, jax.numpy as jnp
import numpy as np

@jax.jit
def program(x):
    return (x @ x.T).sum(axis=1) * 3.0

t0 = time.perf_counter()
program(np.ones((179, 64), dtype=np.float32)).block_until_ready()
elapsed = time.perf_counter() - t0
print(json.dumps({
    "compiles": compilecache.compiles_total(),
    "cache_hits": compilecache.cache_hits_total(),
    "elapsed": elapsed,
    "cache_dir": compilecache.cache_dir(),
    "entries": sorted(f for f in os.listdir(sys.argv[1]) if f.endswith("-cache")),
}))
"""

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_cache_probe(tmp_path, cache_dir, placed_by: str, env_dir=None):
    """One fresh process through ``compilecache.configure`` with the cache
    directory placed by the config key, the environment, or nothing."""
    script = tmp_path / "probe.py"
    script.write_text(_CACHE_PROBE)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_ENABLE_COMPILATION_CACHE"] = "true"  # conftest turns it off
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    # the probe lives in tmp: python only adds the SCRIPT's dir to
    # sys.path, so the repo must come via PYTHONPATH
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, str(script), str(cache_dir), placed_by],
        capture_output=True, text=True, timeout=180, env=env, cwd=REPO_ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_cache_dir_precedence_env_then_key_then_checkout(tmp_path):
    """ISSUE 21 §5: ``JAX_COMPILATION_CACHE_DIR`` set → jax's own reading
    stands and the code sets nothing (not even when the key is set too);
    the key set → the key; neither → ``<checkout>/.jax_cache``."""
    env_dir, key_dir = tmp_path / "from-env", tmp_path / "from-key"
    env_dir.mkdir()
    key_dir.mkdir()
    # environment beats the key: entries land in env_dir, key_dir stays empty
    got = _run_cache_probe(tmp_path, key_dir, "key", env_dir=env_dir)
    assert got["cache_dir"] == str(env_dir)
    assert got["entries"] == [] and os.listdir(env_dir)
    # the key alone is test_persistent_cache_hit_across_processes below;
    # neither: the fixed directory beside the package (the next test runs
    # under it); with the environment set, nothing is written there
    from oryx_tpu.common import compilecache

    assert compilecache.DEFAULT_CACHE_DIR == os.path.join(REPO_ROOT,
                                                          ".jax_cache")
    default_dir = tmp_path / "checkout" / ".jax_cache"
    default_dir.mkdir(parents=True)
    _run_cache_probe(tmp_path, default_dir, "default", env_dir=env_dir)
    assert os.listdir(default_dir) == []


def test_default_cache_shared_by_two_fresh_processes(tmp_path):
    """Two fresh processes under the DEFAULT placement (no key, no
    environment) share entries: the second records a hit on what the first
    wrote — the property the chip tool's one-command, several-process runs
    rely on."""
    default_dir = tmp_path / "checkout" / ".jax_cache"
    first = _run_cache_probe(tmp_path, default_dir, "default")
    assert first["cache_dir"] == str(default_dir)
    assert first["cache_hits"] == 0 and first["entries"]
    second = _run_cache_probe(tmp_path, default_dir, "default")
    assert second["cache_hits"] >= 1, second
    assert second["entries"] == first["entries"]


def test_persistent_cache_hit_across_processes(tmp_path):
    """A second same-config process must reuse the first's XLA binary:
    asserted structurally (same cache-dir entry set, a recorded cache hit)
    and as faster-than-cold."""
    cache_dir = tmp_path / "xla-cache"
    cache_dir.mkdir()

    def run():
        return _run_cache_probe(tmp_path, cache_dir, "key")

    first = run()
    assert first["cache_dir"] == str(cache_dir)
    assert first["compiles"] >= 1
    assert first["cache_hits"] == 0
    assert first["entries"], "first process wrote no cache entries"

    second = run()
    assert second["cache_hits"] >= 1, second  # served from the disk cache
    assert second["entries"] == first["entries"]  # reused, nothing re-keyed
    # no wall-clock comparison: on the tiny CI probe, tracing dominates the
    # XLA compile and scheduler noise swamps the saved time. The recorded
    # cache hit IS jax's own compile-was-skipped signal, and the entry-set
    # equality proves the second process re-keyed nothing.


# ---------------------------------------------------------------------------
# prewarmed model-generation swap
# ---------------------------------------------------------------------------


def _train_model(tmp_path, features: int, seed: int):
    from oryx_tpu.models.als import data as d
    from oryx_tpu.models.als import pmml_codec
    from oryx_tpu.models.als import train as tr

    rng = np.random.default_rng(seed)
    scores = rng.standard_normal((25, 3)) @ rng.standard_normal((3, 15))
    lines = []
    for u in range(25):
        for i in np.argsort(-scores[u])[:5]:
            lines.append(f"u{u},i{i},1,{u * 100 + int(i)}")
    batch = d.prepare(lines, implicit=True)
    x, y = tr.als_train(batch, features=features, lam=0.001, alpha=1.0,
                        implicit=True, iterations=3, chunk=256)
    pmml = pmml_codec.model_to_pmml(
        np.asarray(x), np.asarray(y), batch.users.index_to_id,
        batch.items.index_to_id, features, 0.001, 1.0, True, False, 1e-5,
        tmp_path,
    )
    known = {}
    for it in d.parse_lines(lines):
        known.setdefault(it.user, []).append(it.item)
    return pmml, known


def _publish(pmml, tmp_path, known):
    from oryx_tpu.models.als import pmml_codec
    from oryx_tpu.pmml import pmmlutils

    prod = tp.TopicProducerImpl("memory:", "OryxUpdate")
    prod.send("MODEL", pmmlutils.to_string(pmml))
    for id_, vec in pmml_codec.read_features(tmp_path / "Y"):
        prod.send("UP", json.dumps(["Y", id_, [float(v) for v in vec]]))
    for id_, vec in pmml_codec.read_features(tmp_path / "X"):
        prod.send("UP", json.dumps(
            ["X", id_, [float(v) for v in vec], known.get(id_, [])]
        ))


def test_prewarmed_generation_swap_no_compile_after_flip(tmp_path):
    """A MODEL push with NEW array shapes (features 4 -> 5) during active
    traffic: the old generation keeps serving while the staged one fills
    and warms off-path; after the atomic flip, queries at warmed signatures
    increment the process compile counter by exactly zero."""
    tp.reset_memory_brokers()
    compilecache.warmup_state().reset()
    port = ioutils.choose_free_port()
    config = cfg.overlay_on(
        {
            "oryx.serving.api.port": port,
            "oryx.serving.model-manager-class":
                "oryx_tpu.models.als.serving.ALSServingModelManager",
            "oryx.serving.application-resources":
                "oryx_tpu.serving.resources.als",
            "oryx.serving.compute.precompile-batches": True,
            "oryx.serving.compute.coalesce-max-batch": 8,
        },
        cfg.get_default(),
    )
    tp.maybe_create_topics(config, "input-topic", "update-topic")
    gen1_dir = tmp_path / "gen1"
    gen1_dir.mkdir()
    pmml1, known1 = _train_model(gen1_dir, features=4, seed=0)
    _publish(pmml1, gen1_dir, known1)
    layer = ServingLayer(config)
    layer.start()
    base = f"http://127.0.0.1:{port}"
    try:
        with httpx.Client(base_url=base, timeout=60) as client:
            # gen1 loaded, warmed, serving
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                if (client.get("/readyz").status_code == 200
                        and layer._warmer.warmed_models >= 1):
                    break
                time.sleep(0.1)
            else:
                pytest.fail("gen1 never became warm-ready")
            assert layer.manager.get_model().features == 4

            # hammer /recommend from a side thread THROUGH the swap: every
            # response must come from a loaded generation (200), never a
            # cold-model 503 or an error
            stop = threading.Event()
            statuses: list[int] = []

            def traffic():
                with httpx.Client(base_url=base, timeout=60) as c:
                    while not stop.is_set():
                        statuses.append(
                            c.get("/recommend/u0?considerKnownItems=true")
                            .status_code
                        )

            t = threading.Thread(target=traffic, daemon=True)
            t.start()
            try:
                gen2_dir = tmp_path / "gen2"
                gen2_dir.mkdir()
                pmml2, known2 = _train_model(gen2_dir, features=5, seed=1)
                _publish(pmml2, gen2_dir, known2)
                # the push STAGES gen2; old generation serves until the
                # warmer promotes the warmed staged model
                deadline = time.monotonic() + 90
                while time.monotonic() < deadline:
                    if layer.manager.get_model().features == 5:
                        break
                    time.sleep(0.1)
                else:
                    pytest.fail("staged generation never promoted")
            finally:
                stop.set()
                t.join(timeout=30)
            assert statuses and all(s == 200 for s in statuses), (
                f"traffic saw non-200s across the swap: "
                f"{sorted(set(statuses))}"
            )
            assert layer._warmer.promoted_models >= 1
            assert layer.manager.get_staged_model() is None

            # settle the off-path stragglers BEFORE opening the assertion
            # window: the YtY solver recompute is async (its device compile
            # would land mid-window), so take it blocking here, and one
            # query materializes the current snapshot's programs in case a
            # late UP grew Y after the warm ladder ran
            layer.manager.get_model().get_yty_solver()
            client.get("/recommend/u0?considerKnownItems=true")
            c0 = compilecache.compiles_total()
            # the burst hits the DEFAULT endpoint form — known-item
            # exclusion carried on every request (the program the warmer
            # now precompiles via the shape-stable exclusion width), plus
            # the exclusion-free form; neither may compile post-handoff
            for i in range(10):
                r = client.get(f"/recommend/u{i}")
                assert r.status_code == 200
                assert all(
                    rec["id"] not in known2.get(f"u{i}", [])
                    for rec in r.json()
                )
            for i in range(5):
                r = client.get(f"/recommend/u{i}?considerKnownItems=true")
                assert r.status_code == 200
            assert compilecache.compiles_total() - c0 == 0, (
                "request-path compile after prewarmed generation swap "
                "(first post-handoff /recommend burst, exclusions included)"
            )
    finally:
        layer.close()
        tp.reset_memory_brokers()
        compilecache.warmup_state().reset()


def test_swap_deadline_promotes_unwarmed(tmp_path):
    """If the warmer cannot warm a staged generation (here: it is never
    loaded enough), the swap deadline still promotes it rather than strand
    the model push behind the old generation forever."""
    from oryx_tpu.models.als.serving import ALSServingModelManager

    config = cfg.overlay_on(
        {
            "oryx.serving.compute.precompile-batches": True,
            "oryx.compile.swap-deadline-sec": 0.2,
            "oryx.serving.model-manager-class":
                "oryx_tpu.models.als.serving.ALSServingModelManager",
        },
        cfg.get_default(),
    )
    manager = ALSServingModelManager(config)
    gen1_dir = tmp_path / "g1"
    gen1_dir.mkdir()
    pmml1, _ = _train_model(gen1_dir, features=4, seed=0)
    from oryx_tpu.pmml import pmmlutils

    manager.consume_key_message("MODEL", pmmlutils.to_string(pmml1))
    assert manager.get_model() is not None
    gen2_dir = tmp_path / "g2"
    gen2_dir.mkdir()
    pmml2, _ = _train_model(gen2_dir, features=5, seed=1)
    manager.consume_key_message("MODEL", pmmlutils.to_string(pmml2))
    # staged, old still serving
    assert manager.get_model().features == 4
    assert manager.get_staged_model().features == 5
    time.sleep(0.25)
    assert manager.get_model().features == 5  # deadline valve promoted
    assert manager.get_staged_model() is None
