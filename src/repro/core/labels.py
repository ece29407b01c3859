"""Connected components over the core-grid merge graph.

* ``UnionFind``             -- host path-compression union-find, used by the
                               GriT-DBSCAN-LDF variant (paper §5.2) where the
                               *order* of merge checks matters (low-density
                               first, skip same-set pairs).
* ``label_propagation``     -- device pointer-jumping min-label propagation:
                               the TPU-native equivalent of BFS/union-find
                               (fixed shapes, jit/shard_map-able).
"""

from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp


class UnionFind:
    """Array-based union-find with path compression + union by size."""

    def __init__(self, n: int):
        self.parent = np.arange(n, dtype=np.int64)
        self.size = np.ones(n, dtype=np.int64)

    def find(self, x: int) -> int:
        root = x
        p = self.parent
        while p[root] != root:
            root = p[root]
        while p[x] != root:            # path compression
            p[x], x = root, p[x]
        return root

    def union(self, a: int, b: int) -> bool:
        ra, rb = self.find(a), self.find(b)
        if ra == rb:
            return False
        if self.size[ra] < self.size[rb]:
            ra, rb = rb, ra
        self.parent[rb] = ra
        self.size[ra] += self.size[rb]
        return True

    def labels(self) -> np.ndarray:
        return np.array([self.find(i) for i in range(len(self.parent))])


@partial(jax.jit, static_argnames=("num_nodes_cap",))
def label_propagation(num_nodes_cap: int, edges: jnp.ndarray,
                      edge_valid: jnp.ndarray, node_valid: jnp.ndarray):
    """Min-label propagation + pointer jumping over an undirected edge list.

    Args:
      num_nodes_cap: static node capacity N.
      edges: [E, 2] int32 endpoints (arbitrary values where invalid).
      edge_valid: [E] bool.
      node_valid: [N] bool -- labels of invalid nodes stay = own index.

    Returns labels [N] int32: connected-component representative (min node
    index in component).  The loop runs to its fixpoint: labels only
    decrease, so it terminates, and the fixpoint is the component minimum.
    The round count is data-dependent -- pointer jumping without hooking
    gives no O(log N) bound, and a fixed ``log2 N`` cap left a chain-shaped
    cluster of the seed-spreader workload split in two at n = 1e6.
    """
    N = num_nodes_cap
    u = jnp.where(edge_valid, edges[:, 0], 0)
    v = jnp.where(edge_valid, edges[:, 1], 0)

    def body(state):
        labels, _ = state
        lu, lv = labels[u], labels[v]
        m = jnp.minimum(lu, lv)
        m = jnp.where(edge_valid, m, jnp.int32(N))
        new = labels
        new = new.at[u].min(jnp.where(edge_valid, m, labels[u]))
        new = new.at[v].min(jnp.where(edge_valid, m, labels[v]))
        # pointer jumping: label <- label[label]  (halves tree height)
        new = new[new]
        new = new[new]
        return new, jnp.any(new != labels)

    init_labels = jnp.arange(N, dtype=jnp.int32)
    labels, _ = jax.lax.while_loop(
        lambda s: s[1], body, (init_labels, jnp.ones((), bool)))
    labels = jnp.where(node_valid, labels, jnp.int32(N))
    return labels
