from dryad_tpu.data.columnar import (  # noqa: F401
    Batch, Int64Column, Schema, StringColumn, batch_from_numpy, batch_to_numpy,
    concat_batches, string_column_from_list, string_column_to_list,
)
