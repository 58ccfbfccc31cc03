"""Seconds of set-up in the ScaNN build's pack (the program's
`scann.pack` span: SQ8 range, leaf layout, int8 tiles and row norms,
ended once they are on the device), on the host's clock; None for a
build without it."""


def read(run, trace):
    return run.shape.get("scann_pack_s")
