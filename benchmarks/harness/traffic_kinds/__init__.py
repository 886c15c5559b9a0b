"""One module per traffic `kind`, found by that name (see spec.Cell)."""
