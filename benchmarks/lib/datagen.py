"""Writes the seeded tables as Parquet in the layout the configuration
states (a copy of `chip_smoke.write_parquet`)."""

import os


def write_table(dirpath: str, cols: dict, types: dict, n_files: int,
                groups_per_file: int) -> str:
    """Several files, several row groups each; returns the glob to read.
    A column the schema calls DATE holds days since 1970 and is written as
    Arrow date32."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    os.makedirs(dirpath, exist_ok=True)
    cols = {k: pa.array(v, type=pa.date32() if types[k] == "DATE" else None)
            for k, v in cols.items()}
    rows = len(next(iter(cols.values())))
    per_file = -(-rows // n_files)
    for i in range(n_files):
        sl = slice(i * per_file, min(rows, (i + 1) * per_file))
        pq.write_table(pa.table({k: v[sl] for k, v in cols.items()}),
                       os.path.join(dirpath, f"part-{i:03d}.parquet"),
                       row_group_size=max(1, -(-per_file // groups_per_file)))
    return os.path.join(dirpath, "*.parquet")


def write_tables(work: str, tables: dict, schema: dict, layout: dict) -> dict:
    """{table: glob}"""
    return {name: write_table(os.path.join(work, name), cols, schema[name],
                              int(layout[name]["files"]),
                              int(layout[name]["row_groups_per_file"]))
            for name, cols in tables.items()}
