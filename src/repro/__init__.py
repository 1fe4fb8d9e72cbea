"""Mixture-of-Depths transformers (Raposo et al., 2024) in JAX: models,
routing, training and serving.

The program names its layers with ``jax.named_scope`` (``attention``,
``mod.router``, ``paged.materialize``, ...). The names live in the metadata
of each HLO instruction, where a profiler trace finds them. JAX's persistent
compilation cache leaves that metadata out of its key by default, so an
executable compiled from code without the names could be loaded for code
with them, and its trace would carry none. Importing the package puts the
metadata in the key. Source paths in it are cut to their file names, so the
same code in two checkouts still shares cache entries.
"""
import jax

jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
if jax.config.jax_hlo_source_file_canonicalization_regex is None:
    jax.config.update("jax_hlo_source_file_canonicalization_regex", r".*/")
