"""train_images_per_s: images of all the train steps completed in the
window over the window's length."""


def read(ctx):
    w = ctx["window"]
    return w["calls"] * ctx["work"]["images_per_call"] / w["seconds"]
