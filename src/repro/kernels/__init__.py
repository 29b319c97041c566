"""Pallas kernels of the extraction and analytics hot paths.

One module per kernel (``bloom``, ``sorted_probe``, ``segment_csr``,
``spmv``, ``label_prop``, ``frontier``); :mod:`repro.kernels.ops` holds the
jitted entry points and the kernel-vs-reference policy, and
:mod:`repro.kernels.ref` the pure-jnp oracle each kernel is tested against.
"""
