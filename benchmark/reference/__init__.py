"""Part of the cell benchmark; see ../README.md."""
