"""Shared page-chunk prefix keying (docs/DESIGN.md §20, §23).

The :class:`~zookeeper_tpu.serving.decode.pages.RadixPrefixCache` keys
its trie on FULL ``page_size`` token chunks (one node = one page) with
a longest-common-prefix partial tail, and the fleet router
(docs/DESIGN.md §23) must predict that trie's match length WITHOUT
holding any pages: a router that chunks or walks differently routes
requests to replicas that are not actually warm, silently destroying
the §20 TTFT win. This module is the single source of truth both sides
consume:

- :func:`common_prefix` / :func:`walk_match` / :func:`walk_insert` —
  the chunking + match/insert walks, shared verbatim by the cache's
  ``lookup``/``insert`` and the router's :class:`PrefixIndex`, so the
  two CANNOT drift (the parity test in ``tests/serving/test_fleet.py``
  pins predicted == actual on top). Both take the prompt as a list of
  Python ints (:func:`token_list`, once a prompt) and key only the
  chunks they descend through.
- :class:`PrefixIndex` — the pageless mirror of the trie: the router
  keeps one per replica, ``observe()``-ing every prompt it routes
  there and ``match()``-ing candidate prompts to predict how many
  tokens that replica's REAL cache would serve warm.

Any node object with ``.chunk`` (a token tuple), ``.children`` (a
``{chunk_tuple: node}`` dict) and ``.heads`` (the same children by
their chunk's first token, ``{token: {chunk_tuple: node}}``, kept by
:func:`attach` / :func:`detach`) can ride the walks — the cache's
page-holding nodes and the index's bare nodes both qualify.
"""

from typing import Any, Callable, List, Sequence, Tuple

import numpy as np

__all__ = [
    "PrefixIndex",
    "attach",
    "common_prefix",
    "detach",
    "token_list",
    "walk_insert",
    "walk_match",
]


_NO_HEADS: dict = {}


def token_list(tokens: Sequence[int]) -> List[int]:
    """``tokens`` as the list of Python ints the walks key on: a list
    is taken as it is, anything else (an int32 / int64 array, a tuple)
    goes through ``np.asarray(...).tolist()``, which yields Python
    ints without a conversion an element."""
    if isinstance(tokens, list):
        return tokens
    return np.asarray(tokens).tolist()


def attach(parent: Any, child: Any) -> None:
    """Hang ``child`` under ``parent`` by its chunk, in both maps."""
    parent.children[child.chunk] = child
    parent.heads.setdefault(child.chunk[0], {})[child.chunk] = child


def detach(parent: Any, child: Any) -> None:
    """Take ``child`` from under ``parent`` (the inverse of
    :func:`attach`)."""
    del parent.children[child.chunk]
    same = parent.heads[child.chunk[0]]
    del same[child.chunk]
    if not same:
        del parent.heads[child.chunk[0]]


def common_prefix(a: Sequence[int], b: Sequence[int]) -> int:
    """Length of the longest common prefix of two token sequences."""
    n = min(len(a), len(b))
    for i in range(n):
        if a[i] != b[i]:
            return i
    return n


def walk_match(root: Any, tokens: Sequence[int], page_size: int):
    """The ONE match walk (cache lookup == router prediction): exact
    full-``page_size``-chunk descents from ``root``, then the longest
    common prefix against any child's chunk for the partial tail (only
    the children whose chunk starts with the next token can share any:
    ``node.heads``; the first of the longest wins, in the order the
    children were attached). Returns ``(t, visited)`` — the first ``t``
    tokens are covered by the ``visited`` nodes in walk order (the last
    may cover ``t`` only partially — the cache's CoW case; it is
    partial exactly when ``t % page_size``). ``tokens``: a list of
    ints (:func:`token_list`)."""
    ps = int(page_size)
    node = root
    visited: List[Any] = []
    t = 0
    n = len(tokens)
    while t + ps <= n:
        child = node.children.get(tuple(tokens[t:t + ps]))
        if child is None:
            break
        visited.append(child)
        t += ps
        node = child
    if t < n:
        rest = tokens[t:t + ps]  # no chunk is longer
        best, bestq = None, 0
        for child in node.heads.get(rest[0], _NO_HEADS).values():
            q = common_prefix(child.chunk, rest)
            if q > bestq:
                best, bestq = child, q
        if best is not None:
            visited.append(best)
            t += bestq
    return t, visited


def walk_insert(
    root: Any,
    tokens: Sequence[int],
    page_size: int,
    make_node: Callable[[Tuple[int, ...], int, Any], Any],
    *,
    tail: bool = True,
    start: int = 0,
):
    """The ONE insert walk: descend/create one node per FULL chunk
    (``make_node(chunk, chunk_index, parent)`` builds missing ones),
    plus the partial tail chunk when ``tail`` is set (the cache skips
    it when it has no page covering those positions). ``start`` > 0
    resumes where a match walk of the same ``tokens`` stopped:
    ``root`` is then the node of chunk ``start - 1`` and chunks
    ``start`` on are walked (and keyed) alone. Returns ``[(node,
    created), ...]`` in walk order. ``tokens``: a list of ints
    (:func:`token_list`)."""
    ps = int(page_size)
    node = root
    out: List[Tuple[Any, bool]] = []
    n_full = len(tokens) // ps
    for i in range(start, n_full):
        chunk = tuple(tokens[i * ps:(i + 1) * ps])
        child = node.children.get(chunk)
        created = child is None
        if created:
            child = make_node(chunk, i, node)
            attach(node, child)
        out.append((child, created))
        node = child
    rest = tuple(tokens[n_full * ps:])
    if rest and tail:
        child = node.children.get(rest)
        created = child is None
        if created:
            child = make_node(rest, n_full, node)
            attach(node, child)
        out.append((child, created))
    return out


class _IndexNode:
    __slots__ = ("chunk", "children", "heads")

    def __init__(self, chunk: Tuple[int, ...]) -> None:
        self.chunk = chunk
        self.children = {}
        self.heads = {}


class PrefixIndex:
    """Pageless mirror of the radix prefix-cache trie.

    The fleet router keeps one per replica: every prompt it routes
    there is ``observe()``-d (the replica's cache will insert exactly
    that prompt's pages after prefill), and ``match()`` walks the SAME
    chunking/keying the real cache uses, so the returned length is the
    router's best prediction of the replica's actual warm match.

    Predictions are optimistic by construction — the real cache evicts
    under pool pressure and invalidates on weight swaps while the
    index does not — which only costs a colder-than-predicted route,
    never a wrong answer. ``max_nodes`` bounds router memory: past it
    the index resets to empty (counted in ``resets``) and rewarms from
    subsequent traffic, mirroring a cache that evicted everything.
    """

    def __init__(self, page_size: int, max_nodes: int = 65536) -> None:
        if page_size < 1:
            raise ValueError(f"page_size={page_size} must be >= 1.")
        if max_nodes < 1:
            raise ValueError(f"max_nodes={max_nodes} must be >= 1.")
        self.page_size = int(page_size)
        self.max_nodes = int(max_nodes)
        self._root = _IndexNode(())
        self.nodes = 0
        self.resets = 0

    def observe(self, tokens: Sequence[int]) -> int:
        """Record a prompt routed to this replica (full chunks + the
        partial tail — exactly what the cache's ``insert_prefix``
        caches after prefill). Returns new nodes created."""
        created = sum(
            1
            for _, was_created in walk_insert(
                self._root,
                token_list(tokens),
                self.page_size,
                lambda chunk, i, parent: _IndexNode(chunk),
            )
            if was_created
        )
        self.nodes += created
        if self.nodes > self.max_nodes:
            self.clear()
            self.resets += 1
        return created

    def match(self, tokens: Sequence[int]) -> int:
        """Predicted warm match length for ``tokens`` — the ``t`` the
        replica's real ``RadixPrefixCache.lookup`` would return."""
        t, _ = walk_match(self._root, token_list(tokens), self.page_size)
        return t

    def predict(self, tokens: Sequence[int]) -> int:
        """Predicted SHARED tokens at admission: the match, capped at
        ``len(tokens) - 1`` exactly like ``PagePool.assign_prompt``
        (the final token is always recomputed so the first-emission
        logits exist)."""
        n = len(tokens)
        if n == 0:
            return 0
        return min(self.match(tokens), n - 1)

    def clear(self) -> None:
        self._root = _IndexNode(())
        self.nodes = 0
