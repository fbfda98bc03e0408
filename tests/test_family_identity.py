"""The families that predate interleaved layer kinds serve exactly as
before: the same parameter trees and the same served streams, bit for
bit.

Each case serves three staggered greedy sessions through the
``SlotScheduler`` at reduced sizes in float32 and reduces the result to
three digests: the parameter tree (paths, shapes, dtypes), the token
streams, and every byte of the decode state the scheduler is left with.
The expected digests were read from the tree before layer runs, the
score-scale argument and the Granite scalars were added; any change to
what these families compute, however small, changes the state digest.
"""
import hashlib

import jax
import numpy as np
import pytest

import parity as PT

# arch -> (layout, prefill_chunk)
CASES = {
    "smollm_360m": ("paged", 16),
    "tconst_41m": ("dense", 8),
    "mamba2_130m": ("dense", 8),
    "hymba_1_5b": ("paged", 16),
}

GOLDEN = {
    "hymba_1_5b": (
        "e6004bf19a23bc2409590343ebfb9d80e8d2c94a477630f740b98aa2668202bd",
        "82b774d30b6e2ef432d0ea23ac82eb88b6b57a27161a5f3405825b95b6f58fac",
        "b2e660455aecb3ab64a413282144e3a89398a1dc4bfaff0d312628a7c24d1f83"),
    "mamba2_130m": (
        "75abed681696425e3e2789591336852e993112eacebee56bc3758161669404df",
        "3114c8e3b6c527c92146034a3c7e03be2f171671e5ea485a8860404a22f88191",
        "1394466352fa96db850fccc6cdde772402e3c04233b5ebd64cdb7150d7734ee2"),
    "smollm_360m": (
        "b602c0ab8455d116a1d4a31c116cf0480ec258ea50d1735a429244cd7f301320",
        "820df1f60a73a06f2378cae3fc658eb6d16aeb397959e9bb900eb098528112f5",
        "593cdac625bcfa62549439d563693037b7e50d90d087d9d5c3bd4153af4fe2a7"),
    "tconst_41m": (
        "220eb8e7630fadf38738de08ac43c1ef15fc144860784fe4c64e74ff4435389c",
        "2cce46390a6c91c7c829d74108d58d73558854723bf91aff259ccea3f1bb8a67",
        "6803638618ed44d5f4abccd526b4a5ebbecfef959a1929c2fd7cf2b62dcc2473"),
}


def _sha(chunks) -> str:
    h = hashlib.sha256()
    for c in chunks:
        h.update(c)
    return h.hexdigest()


def fingerprint(arch: str):
    """(tree digest, stream digest, state digest) of one served case."""
    from repro.config import get_config, reduced
    from repro.models.api import build_model
    kind, prefill_chunk = CASES[arch]
    cfg = reduced(get_config(arch), dtype="float32")
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    tree = _sha(f"{jax.tree_util.keystr(p)}:{a.shape}:{a.dtype};".encode()
                for p, a in flat)
    prompts = PT.make_prompts(cfg, (9, 14, 6), seed=5)
    streams, sched = PT.serve_streams(
        cfg, params, prompts, PT.layout_spec(kind), gen=10, slots=2,
        max_len=48, chunk_size=4, prefill_chunk=prefill_chunk)
    toks = _sha(np.asarray(s, np.int32).tobytes() for s in streams)
    leaves = jax.tree_util.tree_flatten_with_path(sched.state)[0]
    state = _sha(jax.tree_util.keystr(p).encode() + np.asarray(a).tobytes()
                 for p, a in leaves)
    return tree, toks, state


@pytest.mark.parametrize("arch", sorted(CASES))
def test_parameter_tree_and_served_state_unchanged(arch):
    assert fingerprint(arch) == GOLDEN[arch]
