"""Names of the profiler spans and scopes of the Study path, in one place.

Host spans (`jax.profiler.TraceAnnotation`) mark the steps of
`Study.run`; device scopes (`jax.named_scope`) name the layers of a sweep
program in the `op_name` of its operations.  Neither records anything
unless a profiler runs: wrap `Study.run()` in `jax.profiler.trace(dir)`.
"""

# host spans; `sweep` carries program, designs, streams, blocks, block,
# layout_rows
STUDY_RUN, STUDY_PLAN, STUDY_CACHE = "study.run", "study.plan", "study.cache"
STUDY_FALLBACK, STUDY_FRAME = "study.fallback", "study.frame"
SWEEP, SWEEP_COLUMNS = "sweep", "sweep.columns"
SWEEP_DISPATCH, SWEEP_FETCH = "sweep.dispatch", "sweep.fetch"

# device scopes of a sweep program
GENERATE, DECODE, REPLAY, STAGES = "generate", "decode", "replay", "stages"
PRECOMPUTE, CHUNK_SCAN, ESCAPE = "precompute", "chunk_scan", "escape"


HOST_SPANS = (STUDY_RUN, STUDY_PLAN, STUDY_CACHE, STUDY_FALLBACK, STUDY_FRAME,
              SWEEP, SWEEP_COLUMNS, SWEEP_DISPATCH, SWEEP_FETCH)
DEVICE_SCOPES = (GENERATE, DECODE, REPLAY, PRECOMPUTE, CHUNK_SCAN, ESCAPE,
                 STAGES)
