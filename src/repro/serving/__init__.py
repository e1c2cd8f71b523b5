"""DLRM serving: ``repro.serving.engine`` holds the two CTR scoring
engines and :func:`~repro.serving.engine.make_dlrm_engine`."""
