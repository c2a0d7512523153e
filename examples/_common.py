"""Shared example bootstrap, imported for its side effect before the
first compile: place the persistent compile cache
(``incubator_mxnet_tpu/utils/compile_cache.py``). The examples run on
whatever backend JAX initialises; a backend that fails to initialise is
an error, not a reason to switch platform."""

from incubator_mxnet_tpu.utils import compile_cache

compile_cache.enable()
