"""Persistent artifact store: integrity, atomicity, and warm-start hydration.

Contracts pinned here (DESIGN.md §13):

1. *Round trip*: a program hydrated from the store is bitwise-identical
   to the fresh synthesis on both ``infer`` and ``for_batch`` paths, with
   the validated SynthesisReport restored.
2. *Zero-iteration warm start*: ``synthesize(artifact_store=...)`` with a
   populated store performs zero fixed-point iterations (registry
   counter) and returns the same fingerprint.
3. *Rejection, never corruption*: truncated, bit-flipped, semantically
   tampered, or schema-version-bumped artifacts read as misses counted in
   ``artifact_invalid_total`` — never a crash, never a silently wrong
   program.
4. *Atomic concurrent puts*: N threads racing ``put_program`` on one
   fingerprint leave exactly one valid artifact and concurrent readers
   never observe a torn state.
5. *Serving L3*: a fresh ProgramCache against a populated store warms
   every bucket with zero Stage-D compiles; executable stamps from a
   foreign jaxlib fall back to plan-only (a miss, not invalid).
"""
import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.artifacts import (ARTIFACT_SCHEMA_VERSION, ArtifactStore,
                             synthesis_request_key)
from repro.cnn import init_network_params
from repro.core import NetworkDescription, run_network, synthesize
from repro.obs import MetricsRegistry, Tracer
from repro.serving import ProgramCache, ReplicaSet, ServingConfig
from repro.serving.loadgen import warm_replicas

jax.config.update("jax_platform_name", "cpu")

MAX_DEG = 0.25


@pytest.fixture(scope="module")
def tiny():
    net = NetworkDescription("artifact_tiny", (3, 8, 8))
    net.conv("c1", 8, 3, padding="SAME", inputs=("input",))
    net.relu("r1")
    net.conv("c2", 8, 3, padding="SAME")
    net.flatten("f")
    net.dense("d1", 4)
    params = init_network_params(net, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 3, 8, 8))
    labels = jnp.argmax(run_network(net, params, x), -1)
    return net, params, x, labels


@pytest.fixture(scope="module")
def fresh_program(tiny):
    net, params, x, labels = tiny
    return synthesize(net, params, validation=(x, labels),
                      max_degradation=MAX_DEG)


# ------------------------------------------------------------ round trip ----
def test_round_trip_bitwise_identical(tiny, fresh_program, tmp_path):
    net, params, x, labels = tiny
    store = ArtifactStore(str(tmp_path))
    fp = store.put_program(fresh_program)
    assert fp == fresh_program.fingerprint()

    loaded = store.load_program(fp)
    assert loaded is not None
    assert loaded.fingerprint() == fp
    # identity: the audit trail survives the disk round trip
    r = loaded.synthesis_report
    assert r is not None and r.validated and r.converged
    assert len(r.iterations) == len(fresh_program.synthesis_report.iterations)
    assert loaded.modes == fresh_program.modes

    # bitwise-identical outputs on both dispatch entry points
    a = np.asarray(fresh_program.infer(x))
    b = np.asarray(loaded.infer(x))
    assert a.tobytes() == b.tobytes()
    xb = np.asarray(x[:4])
    a4 = np.asarray(fresh_program.for_batch(4)(xb))
    b4 = np.asarray(loaded.for_batch(4)(xb))
    assert a4.tobytes() == b4.tobytes()
    assert store.hits == 1 and store.invalid == 0


def test_missing_fingerprint_is_a_miss(tmp_path):
    store = ArtifactStore(str(tmp_path))
    assert store.load_program("deadbeef-cafe") is None
    assert store.misses == 1 and store.invalid == 0


def test_synthesize_store_hit_zero_iterations(tiny, tmp_path):
    net, params, x, labels = tiny
    root = str(tmp_path)

    reg_cold = MetricsRegistry()
    cold = synthesize(net, params, validation=(x, labels),
                      max_degradation=MAX_DEG, registry=reg_cold,
                      artifact_store=ArtifactStore(root, registry=reg_cold))
    assert reg_cold.get("synthesis_iterations_total").value() >= 1

    reg_warm = MetricsRegistry()
    store = ArtifactStore(root, registry=reg_warm)
    warm = synthesize(net, params, validation=(x, labels),
                      max_degradation=MAX_DEG, registry=reg_warm,
                      artifact_store=store)
    assert reg_warm.get("synthesis_iterations_total").value() == 0
    assert warm.fingerprint() == cold.fingerprint()
    assert warm.synthesis_report.validated
    assert store.hits >= 1
    a, b = np.asarray(cold.infer(x)), np.asarray(warm.infer(x))
    assert a.tobytes() == b.tobytes()


def test_different_knobs_never_alias(tiny, tmp_path):
    """The request key covers the synthesis knobs: changing the budget
    must miss rather than hydrate the other request's program."""
    net, params, x, labels = tiny
    root = str(tmp_path)
    synthesize(net, params, validation=(x, labels), max_degradation=MAX_DEG,
               artifact_store=ArtifactStore(root))
    store = ArtifactStore(root)
    k1 = synthesis_request_key(net, params, validation=(x, labels),
                               max_degradation=MAX_DEG)
    k2 = synthesis_request_key(net, params, validation=(x, labels),
                               max_degradation=0.5)
    k3 = synthesis_request_key(net, params, validation=(x, labels),
                               max_degradation=MAX_DEG, allow_int8=True)
    assert len({k1, k2, k3}) == 3


# ------------------------------------------------------- rejection paths ----
def _put(store, program):
    return store.put_program(program)


def test_truncation_rejected(fresh_program, tmp_path):
    store = ArtifactStore(str(tmp_path))
    fp = _put(store, fresh_program)
    weights = os.path.join(store.program_dir(fp), "weights.bin")
    with open(weights, "r+b") as f:
        f.truncate(os.path.getsize(weights) // 2)
    assert store.load_program(fp) is None
    assert store.invalid == 1 and store.stats()["invalid_program"] == 1


def test_bitflip_rejected(fresh_program, tmp_path):
    store = ArtifactStore(str(tmp_path))
    fp = _put(store, fresh_program)
    weights = os.path.join(store.program_dir(fp), "weights.bin")
    blob = bytearray(open(weights, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    with open(weights, "wb") as f:
        f.write(blob)
    assert store.load_program(fp) is None
    assert store.invalid == 1


def test_semantic_tamper_rejected_despite_valid_sha(fresh_program, tmp_path):
    """An attacker who edits program.json AND fixes the manifest sha still
    loses: the recomputed fingerprint no longer matches the artifact's
    identity.  This is the 'silently wrong program' guard."""
    import hashlib
    store = ArtifactStore(str(tmp_path))
    fp = _put(store, fresh_program)
    d = store.program_dir(fp)
    doc = json.load(open(os.path.join(d, "program.json")))
    # flip one layer's vmem budget: plan content changes, shapes don't
    name, lp = next(iter(doc["plan"]["layers"].items()))
    lp["vmem_budget"] = int(lp["vmem_budget"] or 0) + 12345
    raw = (json.dumps(doc, indent=2, sort_keys=True) + "\n").encode()
    with open(os.path.join(d, "program.json"), "wb") as f:
        f.write(raw)
    manifest = json.load(open(os.path.join(d, "manifest.json")))
    manifest["files"]["program.json"] = hashlib.sha256(raw).hexdigest()
    with open(os.path.join(d, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    assert store.load_program(fp) is None
    assert store.invalid == 1


def test_schema_version_bump_rejected(fresh_program, tmp_path):
    store = ArtifactStore(str(tmp_path))
    fp = _put(store, fresh_program)
    path = os.path.join(store.program_dir(fp), "manifest.json")
    manifest = json.load(open(path))
    manifest["schema_version"] = ARTIFACT_SCHEMA_VERSION + 1
    with open(path, "w") as f:
        json.dump(manifest, f)
    assert store.load_program(fp) is None
    assert store.invalid == 1


def test_index_version_bump_reads_as_none(fresh_program, tmp_path):
    store = ArtifactStore(str(tmp_path))
    fp = store.put_program(fresh_program, request_key="req1")
    assert store.lookup("req1") == fp
    path = os.path.join(str(tmp_path), "index", "req1.json")
    with open(path, "w") as f:
        json.dump({"schema_version": ARTIFACT_SCHEMA_VERSION + 1,
                   "fingerprint": fp}, f)
    assert store.lookup("req1") is None
    assert store.invalid == 1


def test_garbage_manifest_never_crashes(fresh_program, tmp_path):
    store = ArtifactStore(str(tmp_path))
    fp = _put(store, fresh_program)
    with open(os.path.join(store.program_dir(fp), "manifest.json"), "w") as f:
        f.write("not json {{{")
    assert store.load_program(fp) is None
    assert store.invalid == 1


# -------------------------------------------------------- concurrent puts ---
def test_concurrent_puts_one_winner_no_torn_reads(fresh_program, tmp_path):
    """N writers race one fingerprint while readers hammer load_program:
    every successful load must be the real program (atomic temp+rename,
    manifest written last), and afterwards exactly one valid artifact
    exists."""
    store = ArtifactStore(str(tmp_path))
    fp = fresh_program.fingerprint()
    n_writers, n_reads = 6, 24
    start = threading.Barrier(n_writers + 1)
    errors = []
    loads = []

    def writer():
        try:
            start.wait(timeout=30.0)
            assert store.put_program(fresh_program) == fp
        except Exception as e:
            errors.append(e)

    def reader():
        try:
            start.wait(timeout=30.0)
            reader_store = ArtifactStore(str(tmp_path))
            for _ in range(n_reads):
                p = reader_store.load_program(fp)
                if p is not None:
                    loads.append(p.fingerprint())
        except Exception as e:
            errors.append(e)

    threads = [threading.Thread(target=writer) for _ in range(n_writers)]
    threads.append(threading.Thread(target=reader))
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120.0)
    assert not errors, errors
    # torn reads would have failed integrity and returned None; every
    # successful read must carry the true identity
    assert all(loaded == fp for loaded in loads)
    assert store.writes == n_writers
    # exactly one artifact directory, and it is valid
    roots = os.listdir(os.path.join(str(tmp_path), "programs"))
    assert roots == [fp]
    final = ArtifactStore(str(tmp_path))
    assert final.load_program(fp) is not None
    assert final.invalid == 0


# ------------------------------------------------------------- serving L3 ---
def test_cache_l3_warm_start_zero_compiles(fresh_program, tmp_path):
    root = str(tmp_path)
    cfg = ServingConfig(max_batch=4, artifact_dir=root)

    cold_reg = MetricsRegistry()
    cold = ReplicaSet(fresh_program, config=cfg, registry=cold_reg)
    warm_replicas(cold)
    assert cold.cache.stats.stage_d_compiles == 3          # buckets 1, 2, 4
    assert cold.cache.store.writes >= 3

    warm_reg = MetricsRegistry()
    warm = ReplicaSet(fresh_program, config=cfg, registry=warm_reg)
    warm_replicas(warm)
    assert warm.cache.stats.stage_d_compiles == 0
    assert warm_reg.get("artifact_hits_total").value(kind="executable") == 3
    x = np.asarray(
        jax.random.normal(jax.random.PRNGKey(7), (1, 3, 8, 8)))
    a = np.asarray(warm.infer_one(x[0]))
    b = np.asarray(fresh_program.infer(x))[0]
    assert a.tobytes() == b.tobytes()


def test_executable_stamp_mismatch_is_plan_only_not_invalid(
        fresh_program, tmp_path):
    store = ArtifactStore(str(tmp_path))
    fp = store.put_program(fresh_program)
    if not store.put_executable(fresh_program, 2):
        pytest.skip("jax.export unavailable on this platform")
    meta_path = os.path.join(store.program_dir(fp), "exec_b2.json")
    meta = json.load(open(meta_path))
    meta["jaxlib"] = "0.0.0-foreign"
    with open(meta_path, "w") as f:
        json.dump(meta, f)
    assert store.load_executable(fresh_program, 2) is None
    stats = store.stats()
    assert stats["invalid_executable"] == 0                # foreign, not bad
    assert stats["misses_executable"] == 1


def test_executable_corruption_is_invalid(fresh_program, tmp_path):
    store = ArtifactStore(str(tmp_path))
    fp = store.put_program(fresh_program)
    if not store.put_executable(fresh_program, 2):
        pytest.skip("jax.export unavailable on this platform")
    bin_path = os.path.join(store.program_dir(fp), "exec_b2.bin")
    blob = bytearray(open(bin_path, "rb").read())
    blob[: 8] = b"\x00" * 8
    with open(bin_path, "wb") as f:
        f.write(blob)
    assert store.load_executable(fresh_program, 2) is None
    assert store.stats()["invalid_executable"] == 1


def test_store_spans_recorded(fresh_program, tmp_path):
    tracer = Tracer()
    store = ArtifactStore(str(tmp_path), tracer=tracer)
    fp = store.put_program(fresh_program)
    assert store.load_program(fp) is not None
    spans = tracer.by_name("serve.artifact_hydrate")
    assert spans and spans[0].attrs["kind"] == "program"


def test_program_cache_store_kwarg_round_trip(fresh_program, tmp_path):
    """Direct ProgramCache(store=...) wiring — write-back then hydrate."""
    store1 = ArtifactStore(str(tmp_path))
    c1 = ProgramCache(store=store1)
    c1.admit(fresh_program)
    built = c1.get_or_build(fresh_program, 2)
    assert built.compile_seconds > 0.0                     # genuinely compiled

    store2 = ArtifactStore(str(tmp_path))
    c2 = ProgramCache(store=store2)
    c2.admit(fresh_program)
    hydrated = c2.get_or_build(fresh_program, 2)
    assert hydrated.compile_seconds == 0.0                 # from disk
    assert c2.stats.stage_d_compiles == 0
    x = np.zeros((2, 3, 8, 8), np.float32)
    assert (np.asarray(built(x)).tobytes()
            == np.asarray(hydrated(x)).tobytes())