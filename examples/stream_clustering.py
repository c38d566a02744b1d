"""Distributed Online Stream Clustering via LSH (paper Fig. 3b, §IV.B).

A JAX implementation of the paper's second case study: posts stream through
Text Cleaning (T0) into a Bucketizer (T1/T2) that applies Locality Sensitive
Hashing — random hyperplane signatures, so near vectors collide with high
probability — and the **dynamic data mapping** pattern routes each
(bucket, post) pair to the Cluster Search pellet owning that bucket
(hash split, same key -> same pellet).  Cluster Search pellets act as local
combiners over their candidate buckets; the Aggregator (T6) picks the global
best cluster per post, and a **feedback loop with choice** (cycle + keyed
split) notifies exactly one Cluster Search pellet to fold the post into its
centroid for future comparisons.

Run:  PYTHONPATH=src python examples/stream_clustering.py
"""
import dataclasses
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro import Flow, FnPellet, KeyedEmit, PullPellet, PushPellet

DIM = 32          # feature dimension ("dictionary of topic words")
N_TABLES = 3      # LSH hash tables (candidate buckets per post)
N_BITS = 6        # hyperplanes per table
N_SEARCH = 3      # Cluster Search pellets (T3, T4, T5)


def make_lsh(seed: int = 0):
    planes = jax.random.normal(jax.random.PRNGKey(seed),
                               (N_TABLES, N_BITS, DIM))

    @jax.jit
    def signatures(v: jnp.ndarray) -> jnp.ndarray:
        bits = (jnp.einsum("tbd,d->tb", planes, v) > 0).astype(jnp.int32)
        weights = 2 ** jnp.arange(N_BITS)
        return jnp.sum(bits * weights, axis=1)     # (N_TABLES,) bucket ids

    return signatures


class TextClean(PushPellet):
    """T0: stemming/stop-words stand-in — L2-normalize the feature vector."""

    def compute(self, post):
        pid, vec = post
        v = jnp.asarray(vec, jnp.float32)
        v = v / (jnp.linalg.norm(v) + 1e-9)
        return (pid, np.asarray(v))


class Bucketizer(PushPellet):
    """T1/T2: apply LSH; emit one keyed message per candidate bucket."""

    def __init__(self):
        self.signatures = make_lsh()

    def compute(self, post):
        pid, v = post
        sigs = np.asarray(self.signatures(jnp.asarray(v)))
        return [KeyedEmit((pid, v, int(t), int(s)), key=(int(t), int(s)))
                for t, s in enumerate(sigs)]


class ClusterSearch(PullPellet):
    """T3-T5: local combiner — nearest centroid among owned buckets.

    State: {bucket_key: (centroid, count)}.  Port "in" receives candidate
    posts (hash-split by bucket); port "update" receives the feedback-loop
    assignment for buckets this pellet owns.
    """

    in_ports = ("in", "update")
    out_ports = ("out",)

    def initial_state(self):
        return {}

    def compute(self, messages, emit, state):
        state = dict(state)
        for m in messages:
            if not m.is_data():
                continue
            if m.port == "feedback":                  # fold post into bucket
                (t, s), v = m.payload
                cen, n = state.get((t, s), (np.zeros(DIM, np.float32), 0))
                state[(t, s)] = ((cen * n + v) / (n + 1), n + 1)
                continue
            pid, v, t, s = m.payload
            cen, n = state.get((t, s), (None, 0))
            if cen is None:
                dist = float("inf")
            else:
                dist = float(np.linalg.norm(cen - v))
            emit((pid, (t, s), dist, v), key=pid)
        return state


class Aggregator(PullPellet):
    """T6: global best cluster per post + feedback with choice."""

    in_ports = ("in",)
    out_ports = ("result", "feedback")

    def initial_state(self):
        return {}

    def compute(self, messages, emit, state):
        state = dict(state)
        for m in messages:
            if not m.is_data():
                continue
            pid, bucket, dist, v = m.payload
            state.setdefault(pid, []).append((dist, bucket, v))
            if len(state[pid]) == N_TABLES:
                cands = sorted(state.pop(pid), key=lambda c: c[0])
                dist, bucket, v = cands[0]
                emit({"post": pid, "cluster": bucket,
                      "dist": None if dist == float("inf") else dist},
                     port="result")
                # feedback loop WITH CHOICE: notify only the winning bucket
                emit((bucket, v), key=bucket, port="feedback")
        return state


def build_flow() -> Flow:
    flow = Flow("lsh-clustering")
    clean = flow.pellet("T0_clean", TextClean, cores=2)
    bucketize = flow.pellet("T1_bucketize", Bucketizer, cores=2)
    searchers = [flow.pellet(f"T{3+i}_search", ClusterSearch)
                 for i in range(N_SEARCH)]
    aggregate = flow.pellet("T6_aggregate", Aggregator)
    sink = flow.pellet("sink", lambda: FnPellet(lambda x: x))
    clean >> bucketize
    for search in searchers:
        # dynamic data mapping: bucket key -> owning search pellet
        bucketize.split("hash") >> search
        # feedback cycle with choice: winning bucket's owner gets the update
        aggregate["feedback"].split("hash") >> search["update"]
        search >> aggregate["in"]
    aggregate["result"] >> sink
    return flow


def synthetic_posts(n_posts: int, n_topics: int = 4, seed: int = 1):
    """Posts drawn around topic centers (ground truth for validation)."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(n_topics, DIM)).astype(np.float32) * 3
    posts, truth = [], []
    for i in range(n_posts):
        topic = int(rng.integers(n_topics))
        vec = centers[topic] + rng.normal(size=DIM).astype(np.float32) * 0.3
        posts.append((i, vec))
        truth.append(topic)
    return posts, truth


def refine_flow(centroids: np.ndarray) -> Flow:
    """Array fast-path refinement pass: re-score every post against the
    final centroids.

    Both stages opt into ``batch(..., array=True)``, so a whole
    micro-batch of post vectors travels the chain as ONE stacked array
    (an ``ArrayBatch`` carrier): the distance stage runs the
    Pallas-backed ``cluster_distance_op`` once per batch — the full
    (B, K) distance matrix in a single device call — and the argmin
    stage consumes the stacked matrix directly.  No per-message
    unstacking between the hops.
    """
    from repro.kernels import ops
    C = jnp.asarray(centroids, jnp.float32)
    interpret = ops.pallas_interpret()

    # sequential: the census below zips assignments against injection
    # order, so carriers must complete in FIFO (data-parallel instances
    # could finish out of order); throughput comes from the batch width
    flow = Flow("lsh-refine")
    dist = flow.pellet("dist", lambda: FnPellet(
        lambda X: ops.cluster_distance_op(jnp.asarray(X, jnp.float32), C,
                                          interpret=interpret),
        vectorized=True, sequential=True))
    dist.batch(128, max_wait_ms=2.0, array=True)
    assign = flow.pellet("assign", lambda: FnPellet(
        lambda D: jnp.argmin(D, axis=1), vectorized=True,
        sequential=True))
    assign.batch(128, array=True)
    sink = flow.pellet("sink", lambda: FnPellet(lambda x: x))
    dist >> assign >> sink
    return flow


def run(n_posts: int = 120, quiet: bool = False):
    flow = build_flow()
    posts, truth = synthetic_posts(n_posts)
    t0 = time.time()
    with flow.session(drain_timeout=120) as s:
        for p in posts:
            s.inject("T0_clean", p)
        results = [p for p in s.results() if isinstance(p, dict)]
        assert not s.errors, s.errors[:3]
        wall = time.time() - t0
        # purity: posts of one topic should mostly share a cluster bucket
        by_cluster: Dict = {}
        for r in results:
            by_cluster.setdefault(r["cluster"], []).append(truth[r["post"]])
        pure = sum(int(np.bincount(np.array(members)).max())
                   for members in by_cluster.values())
        purity = pure / len(results)
        if not quiet:
            print(f"clustered {len(results)} posts into "
                  f"{len(by_cluster)} buckets in {wall:.1f}s "
                  f"({len(results)/wall:,.0f} posts/s), purity={purity:.2f}")

    # -- second pass: array fast-path refinement over the LSH clusters ------
    # centroids = mean vector of each discovered bucket (k largest; tiny
    # buckets are noise — their means sit between topics and would
    # attract everything)
    vec_of = {pid: np.asarray(v, np.float32) for pid, v in posts}
    members_of: Dict = {}
    for r in results:
        members_of.setdefault(r["cluster"], []).append(vec_of[r["post"]])
    top = sorted(members_of.items(), key=lambda kv: -len(kv[1]))[:8]
    top = [kv for kv in top if len(kv[1]) >= max(3, len(results) // 20)] \
        or top[:1]
    centroids = np.stack([np.mean(np.stack(vs), axis=0) for _, vs in top])
    t1 = time.time()
    with refine_flow(centroids).session(drain_timeout=120) as s:
        s.inject_many("dist", [vec_of[r["post"]] for r in results])
        assignments = [int(a) for a in s.results()]
        assert not s.errors, s.errors[:3]
        assert len(assignments) == len(results), \
            f"refine census: {len(assignments)} of {len(results)}"
        refine_wall = time.time() - t1
        by_assigned: Dict = {}
        for r, a in zip(results, assignments):
            by_assigned.setdefault(a, []).append(truth[r["post"]])
        rpure = sum(int(np.bincount(np.array(ms)).max())
                    for ms in by_assigned.values())
        rpurity = rpure / len(assignments)
        if not quiet:
            print(f"refined {len(assignments)} posts against "
                  f"{len(centroids)} centroids in {refine_wall:.2f}s "
                  f"(array fast path, Pallas distance kernel), "
                  f"purity={rpurity:.2f}")
    return {"posts": len(results), "wall_s": wall,
            "clusters": len(by_cluster), "purity": purity,
            "refined_purity": rpurity}


if __name__ == "__main__":
    run()
