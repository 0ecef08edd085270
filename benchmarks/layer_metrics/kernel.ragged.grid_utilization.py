"""Layer: paged_kernel.  Share of the ragged kernel's grid that computes,
%: the program's counters over the window, `generation.step_score_blocks`
(the (descriptor, page, query tile) cells the kernel's skip rule lets
through) over `generation.step_grid_cells` (descriptors x pages bucket x
query tiles: the cells every dispatch's grid holds whatever the batch
holds), both per head and layer.  None off the kernel path, where the
denominator is 0, and from a program without the counter."""


def read(obs):
    c = obs["result"].get("counters", {})
    cells = c.get("generation.step_grid_cells")
    if not cells:
        return None
    return 100.0 * c.get("generation.step_score_blocks", 0) / cells
