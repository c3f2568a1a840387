"""Shared benchmark bootstrap: repo-root import path, platform pinning
under ``JAX_PLATFORMS=cpu``, and the persistent compile cache."""

import os
import sys

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, REPO_ROOT)

import jax

if os.environ.get("JAX_PLATFORMS") == "cpu":
    jax.config.update("jax_platforms", "cpu")


def place_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a directory that is
    the same for every process of a run, and return it.

    ``JAX_COMPILATION_CACHE_DIR`` wins: JAX reads it itself, so nothing
    is set here. Otherwise the cache lives at ``<checkout>/.jax_cache``
    — a fixed path, because the path is part of what a later process
    must agree on to hit. Every program is cached, however quick its
    compile: time from process start to the first step after a resume
    is a headline number, and the entry count is how a run shows
    whether a second process hit the first one's programs.
    """
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
