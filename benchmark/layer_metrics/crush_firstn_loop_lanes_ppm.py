"""Lane-slots that a firstn block's two speculative tries left to the
retry loop, per million lane-slots run: the program's
``firstn_loop_lanes`` over ``firstn_slots`` (``crush/mapper.PERF``, the
driver's deltas over the window). The rule VM runs every slot's first
two tries for every lane at once; a lane whose slot both of them
failed (a collision with an earlier slot's host or device) goes on in
``_choose_one_firstn``'s loop, which runs at the block's full width."""


def read(ctx, variant=None):
    slots = ctx.obs.get("firstn_slots")
    if not slots:                        # a program from before the counter
        return None
    return 1e6 * ctx.obs.get("firstn_loop_lanes", 0) / slots
