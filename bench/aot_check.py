#!/usr/bin/env python3
"""Compile each cell's device programs at the cells' shapes for a described
TPU v5e, without a chip (a rehearsal before chip time is spent).

    JAX_PLATFORMS=cpu python bench/aot_check.py

Compiles, for one chip of a described ``v5e:2x2``, the programs of the
``tpch-z2-m512`` cells (m=512, n_buckets=1024, slots=4, n=2^20): the
``add_many`` build (Pallas ``hash_rank_hist`` + ``rank_hist`` and the
bucketize) at 128 rows and at 94 (the last block of a 2526-day corpus), and
the query path (the reference sketch of one 2^20 vector, its bucketize, and
the all-pairs kernel against the 2526-day corpus padded to D=4096), with
the corpus's slot probabilities.  Prints, per program,
whether it holds a Mosaic kernel and its memory, and exits non-zero if a
compile fails or a program would not fit the chip.  The program code asks
``jax.default_backend()`` where its kernels run; this script steers that to
the TPU for its own process only.
"""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HBM = 16 * 10**9


def main() -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    from repro.kernels import dispatch
    dispatch.on_tpu = lambda: True            # lower the kernels for Mosaic
    from repro.core import priority_sketch
    from repro.kernels import (BucketizedSketch, bucketize, bucketize_corpus,
                               build_priority_corpus, query_corpus,
                               slot_inclusion_probs)

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    f32, i32 = jnp.float32, jnp.int32
    sds = lambda shape, t: jax.ShapeDtypeStruct(shape, t, sharding=chip)

    m, nb, n, D = 512, 1024, 1 << 20, 4096

    def build(A):
        return bucketize_corpus(build_priority_corpus(A, m, 11,
                                                      use_pallas=True),
                                n_buckets=nb, slots=4)

    def query(v, c):
        q = bucketize(priority_sketch(v, m, 11), n_buckets=nb, slots=4)
        return query_corpus(q, c)

    corpus = BucketizedSketch(sds((D, nb, 4), i32), sds((D, nb, 4), f32),
                              sds((D,), f32), sds((D,), i32))
    programs = {
        "build (128, 2^20)": (build, (sds((128, n), f32),)),
        "build (94, 2^20)": (build, (sds((94, n), f32),)),
        "query (sketch of 2^20 + all-pairs, D=4096)": (
            query, (sds((n,), f32), corpus)),
        "slot probabilities D=4096": (slot_inclusion_probs, (corpus,)),
    }
    ok = True
    for name, (fn, args) in programs.items():
        try:
            compiled = jax.jit(fn).lower(*args).compile()
        except Exception as e:   # report every program, then fail
            print(f"FAIL {name}: {type(e).__name__}: {e}"[:2000])
            ok = False
            continue
        mem = compiled.memory_analysis()
        total = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
                 + mem.output_size_in_bytes)
        mosaic = "tpu_custom_call" in compiled.as_text()
        fits = total < HBM
        ok &= fits
        print(f"{'ok  ' if fits else 'FAIL'} {name}: Mosaic kernel {mosaic}; "
              f"arguments {mem.argument_size_in_bytes / 2**20:.1f} MiB, "
              f"temporaries {mem.temp_size_in_bytes / 2**20:.1f} MiB, "
              f"outputs {mem.output_size_in_bytes / 2**20:.1f} MiB")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
