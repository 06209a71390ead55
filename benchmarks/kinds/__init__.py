"""One module per kind of cell (a configuration file names its kind):
``run(cell, args, log) -> result`` drives one whole run of the cell and
returns the fields of the result line. ``<kind>_child`` is the process
that calls ``ray_tpu.init`` and starts what owns the chip."""
