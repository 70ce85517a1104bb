"""Window build: seconds per Mb of draft polished.

The program's ``transformed data into windows`` phase: cutting the
targets into windows and routing every overlap's segments into them."""


def read(ctx):
    v = ctx["registry"].get(
        "phase_seconds_initialize_transformed_data_into_windows")
    return None if v is None else v / ctx["draft_mb"]
