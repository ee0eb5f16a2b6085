"""One module per way of driving the program: ``run(cell, args, rec,
meter, t_start)`` does set-up, warm-up, the window and the correctness
checks, and returns ``correct``, ``attempted``, ``failed``, the
end-to-end values and the context the per-layer readers read."""
